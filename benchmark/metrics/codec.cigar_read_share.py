"""Share of ``codec``'s input reads that lie in molecules with a paired
primary whose CIGAR is not one ``M`` run (a soft clip, an indel), in percent,
over every job of the traced run's window: run-report counters
``codec.reads.cigar`` / ``codec.input_reads``. A count of the input: what the
closed forms of the batch prepare do not cover today, and what a prepare over
CIGARs on the arrays would have to take."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "codec.input_reads" in r.get("metrics", {})]
    reads = sum(m["codec.input_reads"] for m in counted)
    if not reads:
        return None  # a program from before the counters
    return 100.0 * sum(m.get("codec.reads.cigar", 0) for m in counted) / reads
