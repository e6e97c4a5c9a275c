"""Share of ``duplex``'s input reads that lie in molecules the need rule sent
to the per-molecule caller (an X or Y set whose CIGARs differ, or a
mixed-strand set that is no palindrome), in percent, over every job of the
traced run's window: run-report counters ``duplex.reads.filtered`` /
``duplex.input_reads``."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "duplex.reads.filtered" in r.get("metrics", {})]
    reads = sum(m.get("duplex.input_reads", 0) for m in counted)
    if not reads:
        return None  # a program from before the counters, or no such set
    return 100.0 * sum(m["duplex.reads.filtered"] for m in counted) / reads
