"""fgumi-tpu command-line interface.

CLI layer analog of the reference's clap subcommands (/root/reference/src/main.rs:72-221),
argparse-based. One subcommand per tool; shared options grouped like commands/common.rs.
"""

import argparse
import logging
import os
import sys
import time

log = logging.getLogger("fgumi_tpu")


_DEFAULT_SCHEDULER = "balanced-chase-drain"
# the reference's 14 selectable strategies (scheduler/mod.rs:70-178): known
# names are accepted (logged as no-ops); anything else is a loud error so a
# typo cannot silently change nothing
_KNOWN_SCHEDULERS = frozenset({
    "fixed-priority", "chase-bottleneck", "thompson-sampling", "ucb",
    "epsilon-greedy", "thompson-with-priors", "hybrid-adaptive",
    "backpressure-proportional", "two-phase", "sticky-work-stealing",
    "learned-affinity", "optimized-chase", "balanced-chase",
    "balanced-chase-drain"})


def _add_pipeline_compat(p):
    """Reference pipeline-tuning flags, accepted for CLI compatibility.

    The batch engines replace the reference's adaptive worker scheduler
    (scheduler/mod.rs:70-178) and deadlock watchdog (deadlock.rs:1-60) with a
    fixed reader->process->writer stage pipeline over bounded queues, so most
    of these knobs have no behavior to tune here; they parse cleanly (a
    migrating user's scripts keep working) and `_apply_pipeline_compat` maps
    the ones that do have a counterpart (common.rs:625-646,954).
    """
    p.add_argument("--scheduler", default=_DEFAULT_SCHEDULER,
                   metavar="NAME",
                   help="accepted for compatibility; the batch engine uses a "
                        "fixed stage schedule")
    p.add_argument("--pipeline-stats", action="store_true",
                   help="alias for --stats on commands that report a "
                        "per-stage timing table")
    p.add_argument("--deadlock-timeout", type=float, default=60.0,
                   metavar="SECONDS",
                   help="stall-watchdog check interval for threaded runs")
    p.add_argument("--deadlock-recover", action="store_true",
                   help="double queue/byte limits when the watchdog detects "
                        "a stall (reference deadlock.rs:409)")
    p.add_argument("--async-reader", action="store_true",
                   help="accepted for compatibility (the reader thread is "
                        "already asynchronous when --threads >= 2)")
    p.add_argument("--memory-per-thread", default=None, metavar="SIZE",
                   help="per-thread working-set budget; multiplied by the "
                        "thread count into --max-memory when that knob exists")
    p.add_argument("--compression-level", type=int, default=None,
                   metavar="N",
                   help="BGZF level for BAM outputs, 0-12 (reference "
                        "CompressionOptions, default 1; 0 = stored blocks)")


def _apply_pipeline_compat(args):
    """Map accepted compat flags onto this engine's knobs (called once after
    parse_args; commands without the flags are untouched). Returns an exit
    code: 0, or 2 on an unparseable value."""
    from .io import bam as bam_io

    lvl = getattr(args, "compression_level", None)
    if lvl is not None and not 0 <= lvl <= 12:
        log.error("--compression-level %d: must be 0-12", lvl)
        return 2
    # set unconditionally: main() may be called many times in one process
    # (the `pipeline` command chains stages), so a prior stage's level must
    # not leak into the next (context-scoped, so concurrent daemon jobs
    # with different levels stay independent)
    bam_io.set_default_compression_level(lvl)
    if getattr(args, "memory_per_thread", None):
        from .utils.memory import parse_size

        try:
            per = parse_size(args.memory_per_thread)
        except ValueError as e:
            log.error("--memory-per-thread: %s", e)
            return 2
        # reference semantics are per-worker x worker-count (common.rs:954);
        # with no explicit --threads the reference defaults to the core
        # count, so mirror that rather than collapsing to x1
        threads = int(getattr(args, "threads", 0) or 0)
        n = threads if threads > 0 else (os.cpu_count() or 1)
        mm = getattr(args, "max_memory", None)
        if mm is not None and str(mm).strip().lower() != "auto":
            log.info("--memory-per-thread: --max-memory %s set explicitly "
                     "and takes precedence", args.max_memory)
        elif hasattr(args, "max_memory"):
            # explicit byte suffix: a bare number means MiB to parse_size
            args.max_memory = f"{per * n}B"
        else:
            log.info("--memory-per-thread: no memory knob on this command; "
                     "ignored")
    if getattr(args, "scheduler", _DEFAULT_SCHEDULER) != _DEFAULT_SCHEDULER:
        if args.scheduler not in _KNOWN_SCHEDULERS:
            log.error("--scheduler %s: unknown strategy (the reference "
                      "accepts: %s)", args.scheduler,
                      ", ".join(sorted(_KNOWN_SCHEDULERS)))
            return 2
        log.info("--scheduler %s: accepted for compatibility; the batch "
                 "engine uses a fixed reader->process->writer schedule",
                 args.scheduler)
    if getattr(args, "deadlock_recover", False):
        log.info("--deadlock-recover: stall watchdog will double queue/byte "
                 "limits on each stall (reference deadlock.rs:409)")
    if getattr(args, "max_memory", None) is not None:
        # validate once here so every command fails with rc=2 and a clean
        # message, not a traceback from deep inside _stage_kwargs
        from .utils.memory import resolve_budget

        try:
            resolve_budget(args.max_memory)
        except ValueError as e:
            log.error("--max-memory: %s", e)
            return 2
    if getattr(args, "pipeline_stats", False):
        if hasattr(args, "stats"):
            args.stats = True
        else:
            log.info("--pipeline-stats: this command reports no per-stage "
                     "timing table; ignored")
    if getattr(args, "async_reader", False) \
            and int(getattr(args, "threads", 0) or 0) < 2:
        if hasattr(args, "threads"):
            log.info("--async-reader: accepted for compatibility; add "
                     "--threads >= 2 for an asynchronous reader thread")
        else:
            log.info("--async-reader: accepted for compatibility (this "
                     "command reads inline)")
    return 0


def _stage_kwargs(args):
    """run_stages kwargs from the shared pipeline flags: byte-accurate input
    queue governance from --max-memory (reference QueueMemoryOptions,
    commands/common.rs:759-993) and watchdog interval/recovery (deadlock.rs).
    """
    wi = getattr(args, "deadlock_timeout", None)
    kw = {
        # 0 means "watchdog off" (run_stages contract), so no `or`-defaulting
        "watchdog_interval": 120.0 if wi is None else wi,
        "deadlock_recover": getattr(args, "deadlock_recover", False),
    }
    mm = getattr(args, "max_memory", None)
    if mm is not None:
        from .utils.memory import resolve_budget

        # half the budget governs queued input batches; the rest covers the
        # process stage's padded device arrays and pending output chunks
        kw["max_bytes"] = max(resolve_budget(mm) // 2, 1 << 20)
        # a queued batch's working set: decompressed buffer + decoded SoA
        # columns + padded device gathers ~= 3x the raw bytes
        kw["item_bytes"] = lambda b: 3 * b.buf.nbytes
    return kw


def _consensus_stage_kwargs(args):
    """_stage_kwargs + resolve-pool sizing for device-attached consensus
    runs: >=2 resolve workers so a worker blocked on a device fetch never
    starves a host-engine (hybrid) chunk queued behind it. Host-only runs
    keep the threads-3 default (no point oversubscribing pure CPU work).
    Only for commands that pass a real resolve_fn (simplex/duplex/codec) —
    a pool applying the identity is pure queue overhead."""
    kw = _stage_kwargs(args)
    from .ops.kernel import use_host_engine

    if not use_host_engine():
        kw["resolve_workers"] = max(getattr(args, "threads", 0) - 3, 2)
    return kw


def _print_stats(stats, wall_s=None):
    """--stats output: per-stage busy/blocked table + queue occupancy,
    peak RSS, which platform did the consensus work (jax's devices, or the
    native host engine) with the device-boundary accounting (dispatches,
    fetch-wait, GFLOP/s, MFU estimate) and the per-dispatch device
    timeline when any kernel dispatched this run (the
    PipelineStats::format_summary analog, reference base.rs:3379-3947)."""
    print(stats.format_table())
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM"):
                    print(f"peak RSS   {line.split()[1]} kB")
                    break
    except OSError:
        pass
    from .ops.kernel import DEVICE_STATS

    print(DEVICE_STATS.format_summary(wall_s))
    if DEVICE_STATS.dispatches:
        tl = DEVICE_STATS.timeline_snapshot()
        done = [t for t in tl if "t_fetched" in t]
        if done:
            lats = sorted(t["t_fetched"] - t["t_dispatch"] for t in done)
            mid = lats[len(lats) // 2]
            print(f"device timeline: {len(done)} dispatches resolved, "
                  f"latency p50 {mid:.3f}s max {lats[-1]:.3f}s")
            for t in done[:12]:
                print(f"  t+{t['t_dispatch']:7.3f}s  up {t['up_bytes']:>9}B"
                      f"  -> fetched t+{t['t_fetched']:7.3f}s"
                      f"  down {t.get('down_bytes', 0):>8}B"
                      f"  wait {t.get('fetch_wait_s', 0.0):.3f}s")
            if len(done) > 12:
                print(f"  ... {len(done) - 12} more")


def _cmdline() -> str:
    """The command line recorded in output provenance (@PG CL, metric
    headers): the serve daemon overrides it per job with the *client's*
    argv (observe.scope.command_argv) so daemon-run outputs are
    byte-identical to the same command run standalone; outside a job it is
    plain ``sys.argv``."""
    from .observe.scope import current_argv

    return " ".join(current_argv())


def _unmapped_consensus_header(read_group_id: str):
    """Unmapped-consensus output header: no reference sequences, single RG,
    @PG capturing the command line (consensus_runner.rs:115+)."""
    from .io.bam import BamHeader

    return BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n"
             f"@RG\tID:{read_group_id}\tSM:sample\n"
             "@PG\tID:fgumi-tpu\tPN:fgumi-tpu\tCL:" + _cmdline() + "\n",
        ref_names=[], ref_lengths=[])


def _build_dp_mesh(devices_arg, mesh_spec=None):
    """A (dp, sp) mesh over the requested device count, or None (<=1 device).

    Shape resolution, most specific wins (docs/multi-chip.md):

    1. ``--mesh`` / ``FGUMI_TPU_MESH``: ``dpNxspM`` forces an exact shape
       (validated against the live device count with a loud error),
       ``auto`` uses every visible device, ``off`` disables the mesh.
    2. Otherwise the legacy surface: ``--devices`` (count) +
       ``FGUMI_TPU_SP`` (read-axis split; dp = n // sp, default sp=1).

    Sharding is transparent — single-device output is byte-identical
    (tests/test_mesh.py, tools/mesh_smoke.py). Raises
    :class:`~fgumi_tpu.parallel.mesh.MeshConfigError` on an unsatisfiable
    shape; commands map it to exit 2.
    """
    raw_spec = (mesh_spec if mesh_spec is not None
                else os.environ.get("FGUMI_TPU_MESH"))
    # CPU pinned without a forced virtual device count => exactly one device:
    # skip the jax import/backend init entirely (host-engine cold-start
    # path) — unless an explicit mesh shape demands validation. Decided
    # before parallel.mesh is touched: it imports jax at module scope.
    if (raw_spec is None
            and os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
            and "host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")
            and not os.environ.get("FGUMI_TPU_COORDINATOR")):
        return None
    from .ops.kernel import _ensure_jax

    _ensure_jax()  # the guarded first import (stage threads race here)
    from .parallel.mesh import parse_mesh_spec, publish_mesh, resolve_mesh

    spec = parse_mesh_spec(raw_spec)
    if raw_spec is not None and spec is None:  # explicit "off"
        return None
    # multi-host: join the process group BEFORE the first backend touch so
    # jax.devices() below is the global device list (parallel/distributed.py)
    from .parallel.distributed import initialize_from_env
    from .parallel.mesh import MeshConfigError

    dist = initialize_from_env()
    import jax

    devs = jax.devices()
    sp_env = os.environ.get("FGUMI_TPU_SP", "1")
    sp = max(int(sp_env), 1) if sp_env.isdigit() else 1
    if dist:
        # every process must participate with all of its local devices
        # (shard_map cannot run on a mesh missing the caller's devices),
        # and sp groups must stay on one host's ICI — make_global_mesh
        # enforces both; an explicit --devices count cannot apply here
        if devices_arg not in (None, "auto") and int(devices_arg) != len(devs):
            log.warning("--devices %s ignored in multi-host mode: the mesh "
                        "uses all %d global devices", devices_arg, len(devs))
        explicit_sp = False
        if isinstance(spec, tuple):
            dp_req, sp_req = spec
            if dp_req * sp_req != len(devs):
                raise MeshConfigError(
                    f"FGUMI_TPU_MESH=dp{dp_req}xsp{sp_req} does not cover "
                    f"the {len(devs)}-device process group; multi-host "
                    "meshes always use every global device")
            sp = sp_req
            explicit_sp = True
        local = len(jax.local_devices())
        if local % sp != 0:
            if explicit_sp:
                # the --mesh contract: a forced shape is honored exactly
                # or fails loudly — never silently rebuilt with sp=1
                raise MeshConfigError(
                    f"FGUMI_TPU_MESH sp={sp} does not divide the per-host "
                    f"device count {local}; sp groups must stay on one "
                    "host's ICI")
            log.warning("FGUMI_TPU_SP=%d does not divide the per-host "
                        "device count %d; using sp=1", sp, local)
            sp = 1
        from .parallel.distributed import make_global_mesh

        mesh = make_global_mesh(sp=sp)
        publish_mesh(mesh)
        return mesh
    if spec is not None:
        mesh = resolve_mesh(devs, spec, sp_default=sp)
        if mesh is not None:
            publish_mesh(mesh)
        return mesh
    n = len(devs) if devices_arg in (None, "auto") else int(devices_arg)
    n = max(1, min(n, len(devs)))
    if n <= 1:
        return None
    if n % sp != 0:
        log.warning("FGUMI_TPU_SP=%d does not divide device count %d; "
                    "using sp=1", sp, n)
        sp = 1
    from .parallel.mesh import make_mesh

    mesh = make_mesh(devs[:n], sp=sp)
    publish_mesh(mesh)
    return mesh


def _devices_arg(s: str):
    if s == "auto":
        return s
    try:
        return int(s)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'auto' or an integer device count, got {s!r}")


def _parse_bool(s: str) -> bool:
    """fgbio-style boolean flag values (commands/common.rs parse_bool)."""
    if s.lower() in ("true", "t", "yes", "y", "1"):
        return True
    if s.lower() in ("false", "f", "no", "n", "0"):
        return False
    raise argparse.ArgumentTypeError(f"expected true/false, got {s!r}")


def _add_device_filter_opts(p):
    """--device-filter option group shared by the consensus commands: fuse
    the consensus-read filter into the calling command (ISSUE 11). Same
    option grammar/defaults as the standalone ``filter`` command."""
    g = p.add_argument_group(
        "fused filtering",
        "fuse `filter` into this command: consensus columns stay "
        "device-resident, per-read verdicts come from a fused mask "
        "kernel, and only surviving records are fetched + serialized "
        "(byte-identical to piping through `fgumi-tpu filter`)")
    g.add_argument("--device-filter", action="store_true",
                   help="enable the fused consensus→filter stage "
                        "(FGUMI_TPU_DEVICE_FILTER=1 is equivalent)")
    g.add_argument("--filter-min-reads", default="3",
                   help="filter --min-reads (1-3 comma-separated values)")
    g.add_argument("--filter-max-read-error-rate", default="0.025",
                   help="filter --max-read-error-rate")
    g.add_argument("--filter-max-base-error-rate", default="0.1",
                   help="filter --max-base-error-rate")
    g.add_argument("--filter-min-base-quality", type=int, default=None,
                   help="filter --min-base-quality")
    g.add_argument("--filter-min-mean-base-quality", type=float,
                   default=None, help="filter --min-mean-base-quality")
    g.add_argument("--filter-max-no-call-fraction", type=float, default=0.2,
                   help="filter --max-no-call-fraction")
    g.add_argument("--filter-by-template", nargs="?", const=True,
                   default=True, type=_parse_bool,
                   help="drop the whole template when any primary fails")


def _log_filter_stats(stats, label: str):
    log.info("%s filter: %d records -> kept %d, rejected %d, masked %d "
             "bases", label, stats.total_records, stats.passed_records,
             stats.failed_records, stats.bases_masked)
    if stats.rejection_reasons:
        log.info("rejections (filter): %s",
                 dict(stats.rejection_reasons.most_common()))


def _add_shard_opts(p):
    """Scatter sub-job option group shared by the consensus commands (and
    forwarded by `pipeline` to its simplex stage): process only shard K of
    an N-way content-hash split of the grouped input (core/sharding.py;
    docs/serving.md "Scatter/gather")."""
    g = p.add_argument_group(
        "scatter sharding",
        "run as one shard of a scattered whale job (`balance --scatter` "
        "plans these): the grouped input streams through a deterministic "
        "content-hash family filter, and a sidecar manifest records the "
        "kept families' global ordinals for the byte-deterministic gather "
        "merge")
    g.add_argument("--shard", default=None, metavar="K/N",
                   help="keep only MI families hashing to slot K of an "
                        "N-way split (0-based; e.g. 1/4)")
    g.add_argument("--shard-by", choices=["umi", "coord"], default="umi",
                   help="shard axis: umi = numeric MI value hash, coord = "
                        "both-ends template position hash (default umi)")
    g.add_argument("--shard-manifest", default=None, metavar="PATH",
                   help="write the kept-family (ordinal, MI) manifest "
                        "sidecar here (required by the gather stage)")
    g.add_argument("--pg-argv", default=None, metavar="CMDLINE",
                   help="record THIS command line (shlex-quoted) in output "
                        "provenance (@PG CL) instead of the actual argv, so "
                        "shard outputs carry the whale job's provenance and "
                        "gather merges byte-identically")


def _shard_filter_from_args(args):
    """ShardFilter from the --shard option group, or None. Raises
    ValueError (caller logs + exits 2) on a malformed spec."""
    spec_arg = getattr(args, "shard", None)
    if not spec_arg:
        return None
    from .core.sharding import ShardFilter, parse_shard_arg

    spec = parse_shard_arg(spec_arg, getattr(args, "shard_by", "umi"))
    return ShardFilter(spec, getattr(args, "shard_manifest", None))


def _add_simplex(sub):
    p = sub.add_parser("simplex", help="Call simplex consensus reads over MI groups")
    p.add_argument("-i", "--input", required=True, help="grouped BAM (MI tags)")
    p.add_argument("-o", "--output", required=True, help="output consensus BAM")
    p.add_argument("--tag", default="MI")
    p.add_argument("--read-name-prefix", default="fgumi")
    p.add_argument("--read-group-id", default="A")
    p.add_argument("--error-rate-pre-umi", type=int, default=45)
    p.add_argument("--error-rate-post-umi", type=int, default=40)
    p.add_argument("--min-input-base-quality", type=int, default=10)
    p.add_argument("--min-reads", type=int, default=1)
    p.add_argument("--max-reads", type=int, default=None)
    p.add_argument("--min-consensus-base-quality", type=int, default=40)
    p.add_argument("--trim", action="store_true")
    p.add_argument("--no-per-base-tags", action="store_true")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--allow-unmapped", action="store_true")
    p.add_argument("--rejects", default=None,
                   help="optional BAM for raw reads that contribute to no "
                        "consensus (secondary output stream)")
    p.add_argument("--consensus-call-overlapping-bases", type=_parse_bool,
                   nargs="?", const=True, default=True, metavar="true|false",
                   help="pre-correct R1/R2 insert-overlap bases before UMI "
                        "consensus (default true)")
    p.add_argument("--em-seq", action="store_true",
                   help="EM-Seq methylation-aware calling (requires --ref); "
                        "emits MM/ML and cu/ct tags")
    p.add_argument("--taps", action="store_true",
                   help="TAPS methylation-aware calling (requires --ref)")
    p.add_argument("--methylation-mode", choices=["em-seq", "taps"],
                   default=None,
                   help="reference spelling of --em-seq/--taps")
    p.add_argument("--ref", default=None,
                   help="reference FASTA (required for --em-seq/--taps)")
    p.add_argument("--batch-groups", type=int, default=2000,
                   help="MI groups per device batch (classic engine)")
    p.add_argument("--batch-bytes", type=int, default=16 << 20,
                   help="decompressed bytes per record batch (fast engine)")
    p.add_argument("--threads", type=int, default=0,
                   help=">=2 adds reader/writer threads around the "
                        "processing thread (pipeline.run_stages); 0/1 runs "
                        "inline (single-threaded fast path)")
    p.add_argument("--stats", action="store_true",
                   help="print per-stage busy/blocked timing table")
    p.add_argument("--max-memory", default="auto",
                   help="pipeline working-set budget (MiB count, human size, "
                        "or auto): governs queue depths relative to "
                        "--batch-bytes")
    p.add_argument("--classic", action="store_true",
                   help="force the per-record Python engine (the semantic "
                        "reference for the vectorized fast engine)")
    p.add_argument("--devices", default="auto", type=_devices_arg,
                   help="device count for data-parallel consensus dispatch: "
                        "auto (all visible), or an explicit N; 1 disables "
                        "sharding (fast engine only)")
    _add_device_filter_opts(p)
    _add_shard_opts(p)
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_simplex)


def cmd_simplex(args, source=None, sink=None):
    from .consensus.vanilla import VanillaConsensusCaller, VanillaOptions
    from .core.grouper import consensus_pregroup_keep, iter_mi_group_batches
    from .io.bam import BamHeader, BamReader, BamWriter

    # mirrors the reference's argument validation (simplex.rs:521-526)
    if args.min_reads < 1:
        log.error("--min-reads must be >= 1 (a value of 0 admits empty groups)")
        return 2
    if args.max_reads is not None and args.max_reads < args.min_reads:
        log.error("--max-reads (%d) must be >= --min-reads (%d)",
                  args.max_reads, args.min_reads)
        return 2

    opts = VanillaOptions(
        tag=args.tag,
        error_rate_pre_umi=args.error_rate_pre_umi,
        error_rate_post_umi=args.error_rate_post_umi,
        min_input_base_quality=args.min_input_base_quality,
        min_reads=args.min_reads,
        max_reads=args.max_reads,
        produce_per_base_tags=not args.no_per_base_tags,
        seed=args.seed,
        trim=args.trim,
        min_consensus_base_quality=args.min_consensus_base_quality,
    )
    if args.methylation_mode == "em-seq":
        args.em_seq = True
    elif args.methylation_mode == "taps":
        args.taps = True
    if args.em_seq and args.taps:
        log.error("--em-seq and --taps are mutually exclusive")
        return 2
    reference = None
    if args.em_seq or args.taps:
        if args.ref is None:
            log.error("--ref is required with --em-seq/--taps")
            return 2
        from .core.reference import ReferenceReader

        opts.methylation_mode = "em-seq" if args.em_seq else "taps"
        try:
            reference = ReferenceReader(args.ref)
        except OSError as e:
            log.error("cannot read reference %s: %s", args.ref, e)
            return 2

    from .native import batch as nb

    use_fast = nb.available() and not args.classic
    if source is not None and not use_fast:
        log.error("simplex: fused chain requires the native batch engine")
        return 2
    filter_stage = None
    filter_tap = None
    from .consensus.device_filter import device_filter_requested

    if device_filter_requested(args):
        from .consensus.device_filter import (HostFilterTap,
                                              SimplexFilterStage,
                                              filter_config_from_args)

        try:
            fcfg = filter_config_from_args(args)
        except ValueError as e:
            log.error("%s", e)
            return 2
        if use_fast:
            filter_stage = SimplexFilterStage(fcfg, opts,
                                              args.filter_by_template)
        else:
            # classic engine: fused in-process filtering via the record tap
            filter_tap = HostFilterTap(fcfg, args.filter_by_template)
    oc_caller = None
    if args.consensus_call_overlapping_bases:
        from .consensus.overlapping import OverlappingBasesConsensusCaller

        oc_caller = OverlappingBasesConsensusCaller("consensus", "consensus")
    out_header = _unmapped_consensus_header(args.read_group_id)
    try:
        shard = _shard_filter_from_args(args)
    except ValueError as e:
        log.error("%s", e)
        return 2

    t0 = time.monotonic()
    if use_fast:
        from .consensus.fast import FastSimplexCaller, resolve_chunk
        from .io.batch_reader import BamBatchReader
        from .pipeline import StageTimes, run_stages

        from .utils.memory import resolve_budget

        try:
            budget = resolve_budget(args.max_memory)
        except ValueError as e:
            log.error("%s", e)
            return 2
        # each queued item holds ~3x batch-bytes (decompressed chunk + padded
        # device gathers); two queues bound the in-flight working set
        queue_items = int(max(1, min(8, budget // (6 * args.batch_bytes))))
        stats = StageTimes()
        mesh = _build_dp_mesh(getattr(args, "devices", "auto"),
                              getattr(args, "mesh", None))
        with (BamBatchReader(args.input, target_bytes=args.batch_bytes)
              if source is None else source) as reader:
            caller = VanillaConsensusCaller(
                args.read_name_prefix, args.read_group_id, opts,
                reference=reference, ref_names=reader.header.ref_names,
                track_rejects=args.rejects is not None)
            fast = FastSimplexCaller(caller, args.tag.encode(),
                                     overlap_caller=oc_caller, mesh=mesh,
                                     filter_stage=filter_stage)
            allow_unmapped = args.allow_unmapped
            from .utils.progress import ProgressTracker

            progress = ProgressTracker("simplex")
            from .consensus.rejects import RejectsSink

            with RejectsSink(args.rejects, reader.header) as rejects:

                def _process(batch):
                    progress.add(batch.n)
                    out = fast.process_batch(batch, allow_unmapped)
                    rejects.drain(caller)
                    return out

                src = iter(reader)
                if shard is not None:
                    src = shard.wrap_batches(src)
                with (BamWriter(args.output, out_header) if sink is None
                      else sink(out_header)) as writer:
                    # device fetch + thresholds + serialize run as the
                    # parallel resolve stage (threads >= 4: a worker pool
                    # with ordered output; 2-3: on the writer thread), so
                    # they overlap the next batch's host prep
                    run_stages(
                        src, _process, writer.write_serialized,
                        threads=args.threads, queue_items=queue_items,
                        stats=stats, resolve_fn=resolve_chunk,
                        **_consensus_stage_kwargs(args))
                    for blob in fast.flush():
                        writer.write_serialized(resolve_chunk(blob))
                    rejects.drain(caller)
            progress.finish()
        n_out = caller.stats.consensus_reads
        if args.stats:
            _print_stats(stats, time.monotonic() - t0)
    else:
        from .consensus.overlapping import apply_overlapping_consensus

        with BamReader(args.input) as reader:
            caller = VanillaConsensusCaller(
                args.read_name_prefix, args.read_group_id, opts,
                reference=reference, ref_names=reader.header.ref_names,
                track_rejects=args.rejects is not None)
            from .consensus.rejects import RejectsSink

            with RejectsSink(args.rejects, reader.header) as rejects, \
                    BamWriter(args.output, out_header) as writer:
                n_out = 0
                allow_unmapped = args.allow_unmapped
                pregroup = lambda r: consensus_pregroup_keep(r.flag,
                                                             allow_unmapped)
                if shard is not None:
                    # the shard gate runs FIRST: its run tracker must see
                    # every record in stream order, including records the
                    # pregroup would drop (ordinals count ALL families)
                    base_keep = pregroup
                    pregroup = lambda r: shard.record_keep(r) and base_keep(r)
                from .consensus.device_filter import wrap_filter_writer

                writer = wrap_filter_writer(writer, filter_tap)
                for batch in iter_mi_group_batches(
                        reader, args.batch_groups, tag=args.tag.encode(),
                        record_filter=pregroup):
                    if oc_caller is not None:
                        batch = [(umi, apply_overlapping_consensus(
                            recs, oc_caller)) for umi, recs in batch]
                    for rec_bytes in caller.call_groups(batch):
                        writer.write_record_bytes(rec_bytes)
                        n_out += 1
                    rejects.drain(caller)
                if filter_tap is not None:
                    writer.finish()
    if shard is not None:
        shard.write_manifest()
        log.info("simplex shard %s: %d/%d families kept (%d records)",
                 args.shard, len(shard.manifest()), shard.families_seen,
                 shard.records_kept)
    dt = time.monotonic() - t0
    s = caller.stats
    log.info("simplex[%s]: %d input reads -> %d consensus reads in %.2fs "
             "(%.0f reads/s)", "fast" if use_fast else "classic",
             s.input_reads, n_out, dt, s.input_reads / dt if dt else 0)
    if oc_caller is not None and oc_caller.stats.overlapping_bases:
        ocs = oc_caller.stats
        log.info("overlap correction: %d overlapping bases, %d agree, %d disagree, "
                 "%d corrected", ocs.overlapping_bases, ocs.bases_agreeing,
                 ocs.bases_disagreeing, ocs.bases_corrected)
    if s.rejected:
        log.info("rejections: %s", dict(sorted(s.rejected.items())))
    # the caller's account of every input read, in the run report too
    from .observe.metrics import METRICS

    METRICS.inc("simplex.input_reads", s.input_reads)
    METRICS.inc("simplex.consensus_reads", n_out)
    for reason, count in s.rejected.items():
        METRICS.inc("simplex.rejected." + reason, count)
    kf, kt = caller.kernel.fallback_positions, caller.kernel.total_positions
    if kt:
        log.info("kernel fallback rate: %.4f%% (%d/%d positions)",
                 100.0 * kf / kt, kf, kt)
    if filter_stage is not None:
        _log_filter_stats(filter_stage.stats, "simplex")
    elif filter_tap is not None:
        _log_filter_stats(filter_tap.stats, "simplex")
    return 0


def _add_duplex(sub):
    p = sub.add_parser("duplex", help="Call duplex consensus reads over /A+/B MI groups")
    p.add_argument("-i", "--input", required=True, help="grouped BAM (MI tags with /A,/B)")
    p.add_argument("-o", "--output", required=True, help="output consensus BAM")
    p.add_argument("--read-name-prefix", default="fgumi")
    p.add_argument("--read-group-id", default="A")
    p.add_argument("--error-rate-pre-umi", type=int, default=45)
    p.add_argument("--error-rate-post-umi", type=int, default=40)
    p.add_argument("--min-input-base-quality", type=int, default=10)
    p.add_argument("--min-reads", type=int, nargs="+", default=[1],
                   help="1-3 values: total [XY [YX]] (high to low)")
    p.add_argument("--max-reads-per-strand", type=int, default=None)
    p.add_argument("--trim", action="store_true")
    p.add_argument("--no-per-base-tags", action="store_true")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--allow-unmapped", action="store_true")
    p.add_argument("--consensus-call-overlapping-bases", type=_parse_bool,
                   nargs="?", const=True, default=True, metavar="true|false",
                   help="pre-correct R1/R2 insert-overlap bases before UMI "
                        "consensus (default true)")
    p.add_argument("--rejects", default=None,
                   help="optional BAM for raw reads that contribute to no "
                        "consensus (secondary output stream; uses the classic "
                        "engine)")
    p.add_argument("--batch-molecules", type=int, default=1000)
    p.add_argument("--threads", type=int, default=0,
                   help="reader/writer threads around the vectorized engine "
                        "(0/1 = inline)")
    p.add_argument("--batch-bytes", type=int, default=16 << 20,
                   help="decompressed bytes per record batch (fast engine)")
    p.add_argument("--stats", action="store_true",
                   help="print per-stage pipeline timing table")
    p.add_argument("--classic", action="store_true",
                   help="force the per-molecule engine (no batch vectorization)")
    p.add_argument("--devices", default="auto", type=_devices_arg,
                   help="device count for data-parallel SS dispatch: auto "
                        "(all visible) or an explicit N; 1 disables sharding "
                        "(fast engine only)")
    p.add_argument("--methylation-mode", choices=["em-seq", "taps"],
                   default=None,
                   help="EM-Seq/TAPS methylation-aware duplex calling "
                        "(requires --ref); emits per-strand am/au/at + "
                        "bm/bu/bt and combined MM/ML + cu/ct tags")
    p.add_argument("--ref", default=None,
                   help="reference FASTA (required with --methylation-mode)")
    _add_device_filter_opts(p)
    _add_shard_opts(p)
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_duplex)


def cmd_duplex(args):
    from .consensus.duplex import DuplexConsensusCaller, iter_duplex_groups
    from .core.grouper import consensus_pregroup_keep
    from .io.bam import BamHeader, BamReader, BamWriter

    reference = None
    ref_names = None
    if args.methylation_mode:
        if args.ref is None:
            log.error("--ref is required with --methylation-mode")
            return 2
        from .core.reference import ReferenceReader
        from .io.bam import BamReader as _BR

        try:
            reference = ReferenceReader(args.ref)
        except OSError as e:
            log.error("cannot read reference %s: %s", args.ref, e)
            return 2
        with _BR(args.input) as _r:
            ref_names = _r.header.ref_names
    elif args.ref is not None:
        log.error("--ref requires --methylation-mode to be set")
        return 2
    try:
        caller_kw = dict(
            min_reads=args.min_reads,
            min_input_base_quality=args.min_input_base_quality,
            produce_per_base_tags=not args.no_per_base_tags, trim=args.trim,
            max_reads_per_strand=args.max_reads_per_strand,
            error_rate_pre_umi=args.error_rate_pre_umi,
            error_rate_post_umi=args.error_rate_post_umi, seed=args.seed,
            track_rejects=args.rejects is not None,
            methylation_mode=args.methylation_mode,
            reference=reference, ref_names=ref_names)
        caller = DuplexConsensusCaller(args.read_name_prefix,
                                       args.read_group_id, **caller_kw)
    except ValueError as e:
        log.error("%s", e)
        return 2

    from .native import batch as nb

    # the vectorized engine cannot express quality trimming; rejects tracking
    # routes every molecule through the slow fallback, so use the classic
    # loop directly there
    use_fast = (nb.available() and not getattr(args, "classic", False)
                and not args.trim and args.rejects is None)
    from .consensus.device_filter import make_filter_tap, wrap_filter_writer

    try:
        filter_tap = make_filter_tap(args)
    except ValueError as e:
        log.error("%s", e)
        return 2
    try:
        shard = _shard_filter_from_args(args)
    except ValueError as e:
        log.error("%s", e)
        return 2
    t0 = time.monotonic()
    allow_unmapped = args.allow_unmapped
    oc_caller = None
    if args.consensus_call_overlapping_bases:
        from .consensus.overlapping import (OverlappingBasesConsensusCaller,
                                            apply_overlapping_consensus)
        oc_caller = OverlappingBasesConsensusCaller("consensus", "consensus")
    out_header = _unmapped_consensus_header(args.read_group_id)
    if use_fast:
        from .consensus.fast import resolve_chunk
        from .consensus.fast_duplex import FastDuplexCaller
        from .io.batch_reader import BamBatchReader
        from .pipeline import StageTimes, run_stages
        from .utils.progress import ProgressTracker

        stats_t = StageTimes()
        mesh = _build_dp_mesh(getattr(args, "devices", "auto"),
                              getattr(args, "mesh", None))
        fast = FastDuplexCaller(caller, b"MI", overlap_caller=oc_caller,
                                mesh=mesh)
        progress = ProgressTracker("duplex")
        with BamBatchReader(args.input,
                            target_bytes=args.batch_bytes) as reader:

            def _process(batch):
                progress.add(batch.n)
                return fast.process_batch(batch, allow_unmapped)

            src = iter(reader)
            if shard is not None:
                src = shard.wrap_batches(src)
            with BamWriter(args.output, out_header) as writer:
                writer = wrap_filter_writer(writer, filter_tap)
                run_stages(
                    src, _process, writer.write_serialized,
                    threads=args.threads, stats=stats_t,
                    resolve_fn=resolve_chunk, **_consensus_stage_kwargs(args))
                for blob in fast.flush():
                    writer.write_serialized(resolve_chunk(blob))
                if filter_tap is not None:
                    writer.finish()
        progress.finish()
        n_out = caller.stats.consensus_reads
        if args.stats:
            _print_stats(stats_t, time.monotonic() - t0)
    else:
        with BamReader(args.input) as reader:
            from .consensus.rejects import RejectsSink

            with RejectsSink(args.rejects, reader.header) as rejects, \
                    BamWriter(args.output, out_header) as writer:
                writer = wrap_filter_writer(writer, filter_tap)
                n_out = 0
                pregroup = lambda r: consensus_pregroup_keep(r.flag,
                                                             allow_unmapped)
                if shard is not None:
                    # shard gate first: it must see every record in stream
                    # order (same contract as the simplex classic path)
                    base_keep = pregroup
                    pregroup = lambda r: shard.record_keep(r) and base_keep(r)
                batch = []
                for group in iter_duplex_groups(reader,
                                                record_filter=pregroup):
                    if oc_caller is not None:
                        base_mi, a_recs, b_recs = group
                        # skip single-strand groups: no duplex possible anyway
                        # (duplex.rs:496-499 has_both_strands_raw gate)
                        if a_recs and b_recs:
                            group = (base_mi,
                                     apply_overlapping_consensus(a_recs,
                                                                 oc_caller),
                                     apply_overlapping_consensus(b_recs,
                                                                 oc_caller))
                    batch.append(group)
                    if len(batch) >= args.batch_molecules:
                        for rec_bytes in caller.call_groups(batch):
                            writer.write_record_bytes(rec_bytes)
                            n_out += 1
                        rejects.drain(caller)
                        batch = []
                if batch:
                    for rec_bytes in caller.call_groups(batch):
                        writer.write_record_bytes(rec_bytes)
                        n_out += 1
                    rejects.drain(caller)
                if filter_tap is not None:
                    writer.finish()
    if shard is not None:
        shard.write_manifest()
        log.info("duplex shard %s: %d/%d families kept (%d records)",
                 args.shard, len(shard.manifest()), shard.families_seen,
                 shard.records_kept)
    dt = time.monotonic() - t0
    s = caller.merged_stats()
    log.info("duplex[%s]: %d input reads -> %d consensus reads in %.2fs "
             "(%.0f reads/s)", "fast" if use_fast else "classic",
             s.input_reads, n_out, dt, s.input_reads / dt if dt else 0)
    if oc_caller is not None and oc_caller.stats.overlapping_bases:
        ocs = oc_caller.stats
        log.info("overlap correction: %d overlapping bases, %d agree, %d disagree, "
                 "%d corrected", ocs.overlapping_bases, ocs.bases_agreeing,
                 ocs.bases_disagreeing, ocs.bases_corrected)
    if s.rejected:
        log.info("rejections: %s", dict(sorted(s.rejected.items())))
    # the caller's account of every input read, in the run report too
    from .observe.metrics import METRICS

    METRICS.inc("duplex.input_reads", s.input_reads)
    METRICS.inc("duplex.consensus_reads", n_out)
    for reason, count in s.rejected.items():
        METRICS.inc("duplex.reads.rejected." + reason, count)
    caller.fold_filter_tally()
    if filter_tap is not None:
        _log_filter_stats(filter_tap.stats, "duplex")
    return 0


def _add_duplex_metrics(sub):
    p = sub.add_parser("duplex-metrics",
                       help="Collect QC metrics for duplex sequencing (grouped BAM)")
    p.add_argument("-i", "--input", required=True,
                   help="grouped BAM (MI tags with /A,/B, template-coordinate order)")
    p.add_argument("-o", "--output", required=True,
                   help="output path prefix for metric files")
    p.add_argument("--intervals", default=None,
                   help="BED or Picard interval list restricting analysis")
    p.add_argument("--min-ab-reads", type=int, default=1,
                   help="min AB-strand reads for a family to count as duplex")
    p.add_argument("--min-ba-reads", type=int, default=1,
                   help="min BA-strand reads for a family to count as duplex")
    p.add_argument("--duplex-umi-counts", action="store_true",
                   help="also write duplex UMI pair counts (memory intensive)")
    p.add_argument("--description", default=None,
                   help="accepted for compatibility: the reference uses this "
                        "only to title its optional R plot PDFs, which this "
                        "build does not generate (metrics TSVs carry no "
                        "title)")
    p.set_defaults(func=_cmd_duplex_metrics)


def _cmd_duplex_metrics(args):
    from .commands.duplex_metrics import run_duplex_metrics

    return run_duplex_metrics(args)


def _add_simplex_metrics(sub):
    p = sub.add_parser("simplex-metrics",
                       help="Collect QC metrics for simplex sequencing (grouped BAM)")
    p.add_argument("-i", "--input", required=True,
                   help="grouped BAM (MI tags, template-coordinate order)")
    p.add_argument("-o", "--output", required=True,
                   help="output path prefix for metric files")
    p.add_argument("--intervals", default=None,
                   help="BED or Picard interval list restricting analysis")
    p.add_argument("--min-reads", type=int, default=1,
                   help="min family size counted toward ss_consensus_families")
    p.add_argument("--description", default=None,
                   help="accepted for compatibility: the reference uses this "
                        "only to title its optional R plot PDFs, which this "
                        "build does not generate (metrics TSVs carry no "
                        "title)")
    p.set_defaults(func=_cmd_simplex_metrics)


def _cmd_simplex_metrics(args):
    from .commands.simplex_metrics import run_simplex_metrics

    return run_simplex_metrics(args)


def _add_review(sub):
    p = sub.add_parser("review",
                       help="Extract data to review variant calls from "
                            "consensus reads")
    p.add_argument("-i", "--input", required=True,
                   help="VCF or interval list of variant positions")
    p.add_argument("-c", "--consensus-bam", required=True,
                   help="coordinate-sorted consensus BAM")
    p.add_argument("-g", "--grouped-bam", required=True,
                   help="coordinate-sorted grouped raw-read BAM")
    p.add_argument("-r", "--ref", default=None,
                   help="reference FASTA (required for interval-list input)")
    p.add_argument("-o", "--output", required=True,
                   help="output prefix (.consensus.bam/.grouped.bam/.txt)")
    p.add_argument("-s", "--sample", default=None,
                   help="sample name for VCF genotype extraction")
    p.add_argument("-N", "--ignore-ns", type=_parse_bool, nargs="?",
                   const=True, default=False, metavar="true|false",
                   help="ignore N bases in consensus reads")
    p.add_argument("-m", "--maf", type=float, default=0.05,
                   help="only review variants at or below this MAF")
    p.set_defaults(func=_cmd_review)


def _cmd_review(args):
    from .commands.review import run_review

    return run_review(args)


def _add_compare(sub):
    p = sub.add_parser("compare", help="Compare files for testing and validation")
    ps = p.add_subparsers(dest="compare_mode", required=True)
    b = ps.add_parser("bams", help="Compare two BAMs (exit 1 on mismatch)")
    b.add_argument("-a", required=True, help="first BAM")
    b.add_argument("-b", required=True, help="second BAM")
    b.add_argument("--mode", choices=["content", "grouping"], default=None,
                   help="content: exact record compare; grouping: MI-invariant "
                        "molecule equivalence (default: content, or the "
                        "--command preset's mode)")
    b.add_argument("--command", default=None, dest="preset",
                   choices=["extract", "zipper", "sort", "correct", "dedup",
                            "clip", "filter", "group", "simplex", "duplex",
                            "codec"],
                   help="canonical mode/ignore-order defaults for comparing "
                        "the output of one pipeline stage (reference "
                        "compare/bams.rs CommandPreset): group -> grouping "
                        "mode; sort -> the sort-verify engine; everything "
                        "else -> exact content. Explicit --mode/"
                        "--ignore-order override the preset")
    b.add_argument("--ignore-order", type=_parse_bool, nargs="?",
                   const=True, default=None,
                   help="content mode: compare as multisets (true/false; "
                        "an explicit value overrides a --command preset in "
                        "either direction)")
    b.add_argument("--ignore-tags", nargs="*", default=[],
                   help="tags excluded from comparison")
    b.add_argument("--tag", default="MI", help="grouping tag (grouping mode)")
    b.add_argument("--verify-sort", action="store_true",
                   help="also verify each input satisfies its header's "
                        "declared sort order (sort_verify engine)")
    b.set_defaults(func=_cmd_compare_bams)
    m = ps.add_parser("metrics", help="Compare two metric TSVs (exit 1 on mismatch)")
    m.add_argument("-a", required=True)
    m.add_argument("-b", required=True)
    m.add_argument("--float-tolerance", type=float, default=1e-5)
    m.set_defaults(func=_cmd_compare_metrics)


def _cmd_compare_bams(args):
    from .commands.compare import run_compare_bams

    return run_compare_bams(args)


def _cmd_compare_metrics(args):
    from .commands.compare import run_compare_metrics

    return run_compare_metrics(args)


def _add_codec(sub):
    p = sub.add_parser(
        "codec",
        help="Call CODEC consensus (one read-pair covers both strands)")
    p.add_argument("-i", "--input", required=True,
                   help="grouped BAM (MI tags, no /A,/B suffixes)")
    p.add_argument("-o", "--output", required=True, help="output consensus BAM")
    p.add_argument("-r", "--rejects", default=None,
                   help="optional BAM for rejected records")
    p.add_argument("--tag", default="MI")
    p.add_argument("--read-name-prefix", default="fgumi")
    p.add_argument("--read-group-id", default="A")
    p.add_argument("--error-rate-pre-umi", type=int, default=45)
    p.add_argument("--error-rate-post-umi", type=int, default=40)
    p.add_argument("--min-input-base-quality", type=int, default=10)
    p.add_argument("-M", "--min-reads", type=int, default=1,
                   help="min read pairs per strand")
    p.add_argument("--max-reads", type=int, default=None,
                   help="max read pairs per strand (downsample)")
    p.add_argument("-d", "--min-duplex-length", type=int, default=1)
    p.add_argument("--single-strand-qual", type=int, default=None)
    p.add_argument("-Q", "--outer-bases-qual", type=int, default=None)
    p.add_argument("-O", "--outer-bases-length", type=int, default=5)
    p.add_argument("-x", "--max-duplex-disagreement-rate", type=float, default=1.0)
    p.add_argument("-X", "--max-duplex-disagreements", type=int, default=None)
    p.add_argument("--cell-tag", default=None, help="cell barcode tag (e.g. CB)")
    p.add_argument("--per-base-tags", action="store_true",
                   help="emit ad/bd/ae/be/ac/bc/aq/bq tags")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--batch-groups", type=int, default=1000)
    p.add_argument("--batch-bytes", type=int, default=16 << 20,
                   help="decompressed bytes per record batch (fast engine)")
    p.add_argument("--threads", type=int, default=0,
                   help="reader/writer threads around the batch engine "
                        "(0/1 = inline)")
    p.add_argument("--stats", action="store_true",
                   help="print per-stage pipeline timing table")
    p.add_argument("--classic", action="store_true",
                   help="force the per-molecule engine (no batch vectorization)")
    p.add_argument("--devices", default="auto", type=_devices_arg,
                   help="device count for data-parallel SS dispatch: auto "
                        "(all visible) or an explicit N; 1 disables sharding "
                        "(batch engine only)")
    _add_device_filter_opts(p)
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_codec)


def cmd_codec(args):
    from .consensus.codec import CodecConsensusCaller, CodecOptions
    from .core.grouper import iter_mi_group_batches
    from .io.bam import BamHeader, BamReader, BamWriter

    if args.min_reads < 1:
        log.error("--min-reads must be >= 1")
        return 2
    if args.max_reads is not None and args.max_reads < args.min_reads:
        log.error("--max-reads (%d) must be >= --min-reads (%d)",
                  args.max_reads, args.min_reads)
        return 2

    opts = CodecOptions(
        min_input_base_quality=args.min_input_base_quality,
        error_rate_pre_umi=args.error_rate_pre_umi,
        error_rate_post_umi=args.error_rate_post_umi,
        min_reads_per_strand=args.min_reads,
        max_reads_per_strand=args.max_reads,
        min_duplex_length=args.min_duplex_length,
        single_strand_qual=args.single_strand_qual,
        outer_bases_qual=args.outer_bases_qual,
        outer_bases_length=args.outer_bases_length,
        max_duplex_disagreements=args.max_duplex_disagreements,
        max_duplex_disagreement_rate=args.max_duplex_disagreement_rate,
        cell_tag=args.cell_tag,
        produce_per_base_tags=args.per_base_tags,
        seed=args.seed)
    caller = CodecConsensusCaller(args.read_name_prefix, args.read_group_id, opts,
                                  track_rejects=args.rejects is not None)

    from .native import batch as nbat

    # the batch engine shares the classic caller's stage 2 but cannot feed
    # the rejects stream (records stay array-resident); rejects -> classic
    use_fast = (nbat.available() and args.rejects is None
                and not getattr(args, "classic", False))
    from .consensus.device_filter import make_filter_tap, wrap_filter_writer

    try:
        filter_tap = make_filter_tap(args)
    except ValueError as e:
        log.error("%s", e)
        return 2
    if not use_fast and (args.threads or args.stats):
        log.info("--threads/--stats apply to the batch engine only; this "
                 "run uses the classic per-molecule engine (%s)",
                 "--rejects set" if args.rejects is not None
                 else "--classic" if getattr(args, "classic", False)
                 else "native runtime unavailable")
    t0 = time.monotonic()
    if use_fast:
        from .consensus.fast import resolve_chunk
        from .consensus.fast_codec import FastCodecCaller
        from .io.batch_reader import BamBatchReader
        from .pipeline import StageTimes, run_stages
        from .utils.progress import ProgressTracker

        stats_t = StageTimes()
        progress = ProgressTracker("codec")
        mesh = _build_dp_mesh(getattr(args, "devices", "auto"),
                              getattr(args, "mesh", None))
        with BamBatchReader(args.input,
                            target_bytes=args.batch_bytes) as reader:
            out_header = _unmapped_consensus_header(args.read_group_id)
            fast = FastCodecCaller(caller, args.tag.encode(), mesh=mesh)

            def _process(batch):
                progress.add(batch.n)
                return fast.process_batch(batch)

            with BamWriter(args.output, out_header) as writer:
                writer = wrap_filter_writer(writer, filter_tap)
                run_stages(iter(reader), _process, writer.write_serialized,
                           threads=args.threads, stats=stats_t,
                           resolve_fn=resolve_chunk,
                           **_consensus_stage_kwargs(args))
                for chunk in fast.flush():
                    writer.write_serialized(resolve_chunk(chunk))
                if filter_tap is not None:
                    writer.finish()
                n_out = caller.stats.consensus_reads_generated
        progress.finish()
        if args.stats:
            _print_stats(stats_t, time.monotonic() - t0)
    else:
        if nbat.available():
            from .io.batch_reader import BatchedRecordReader as _CodecReader
        else:
            _CodecReader = BamReader
        with _CodecReader(args.input) as reader:
            out_header = _unmapped_consensus_header(args.read_group_id)
            rejects_writer = None
            if args.rejects is not None:
                # rejects keep the input header (raw RG/PG/contig metadata
                # preserved)
                rejects_writer = BamWriter(args.rejects, reader.header)
            ok = False
            try:
                with BamWriter(args.output, out_header) as writer:
                    writer = wrap_filter_writer(writer, filter_tap)
                    n_out = 0
                    for batch in iter_mi_group_batches(
                            reader, args.batch_groups, tag=args.tag.encode()):
                        for rec_bytes in caller.call_groups(batch):
                            writer.write_record_bytes(rec_bytes)
                            n_out += 1
                        if rejects_writer is not None \
                                and caller.rejected_reads:
                            for rec in caller.rejected_reads:
                                rejects_writer.write_record(rec)
                            caller.rejected_reads.clear()
                    if filter_tap is not None:
                        writer.finish()
                ok = True
            finally:
                if rejects_writer is not None:
                    (rejects_writer.close if ok
                     else rejects_writer.discard)()
    dt = time.monotonic() - t0
    s = caller.stats
    log.info("codec: %d input reads -> %d consensus reads in %.2fs (%.0f reads/s)",
             s.total_input_reads, n_out, dt,
             s.total_input_reads / dt if dt else 0)
    if s.rejection_reasons:
        log.info("rejections: %s", dict(sorted(s.rejection_reasons.items())))
    # the caller's account of every input read, in the run report too
    from .observe.metrics import METRICS

    METRICS.inc("codec.input_reads", s.total_input_reads)
    METRICS.inc("codec.consensus_reads", n_out)
    for reason, count in s.rejection_reasons.items():
        METRICS.inc("codec.reads.rejected." + reason, count)
    s.fold_filter_tally()
    if s.consensus_duplex_bases_emitted:
        log.info("duplex disagreement rate: %.6f (%d/%d)",
                 s.duplex_disagreement_rate(), s.duplex_disagreement_base_count,
                 s.consensus_duplex_bases_emitted)
    if filter_tap is not None:
        _log_filter_stats(filter_tap.stats, "codec")
    return 0


def _add_group(sub):
    p = sub.add_parser("group", help="Group reads by UMI (GroupReadsByUmi)")
    p.add_argument("-i", "--input", required=True,
                   help="template-coordinate sorted BAM with RX tags")
    p.add_argument("-o", "--output", required=True)
    p.add_argument("--max-memory", default="auto",
                   help="pipeline working-set budget (MiB count, human "
                        "size, or auto): bytes-in-flight bound on queued "
                        "batches in threaded runs")
    p.add_argument("-s", "--strategy", default="adjacency",
                   choices=["identity", "edit", "adjacency", "paired"])
    p.add_argument("-e", "--edits", type=int, default=1)
    p.add_argument("-t", "--raw-tag", default="RX")
    p.add_argument("-T", "--assign-tag", default="MI")
    p.add_argument("-m", "--min-map-q", type=int, default=1)
    p.add_argument("-n", "--include-non-pf-reads", action="store_true")
    p.add_argument("--min-umi-length", type=int, default=None)
    p.add_argument("--no-umi", action="store_true")
    p.add_argument("--allow-unmapped", action="store_true")
    p.add_argument("--index-threshold", type=int, default=None,
                   help="minimum distinct UMIs per group before the indexed "
                        "candidate search (pigeonhole/BK-tree) replaces the "
                        "dense pairwise scan; 0 = always dense. Default is "
                        "measured for the vectorized scan (8192). A group's "
                        "neighbour graph takes one of three routes by its "
                        "distinct UMIs: under 1,024 a dense numpy compare on "
                        "the host, from 1,024 up to this threshold the "
                        "device Hamming kernel, from the threshold on the "
                        "native index on the host")
    p.add_argument("--parallel-group-min-templates", default=None,
                   metavar="N|auto",
                   help="accepted for compatibility: this engine "
                        "auto-selects its vectorized/device assigner by "
                        "group size, so the parallel-assigner cutover knob "
                        "has no separate schedule to tune")
    p.add_argument("-f", "--family-size-histogram", default=None,
                   help="optional TSV of the family size distribution "
                        "(fgbio format: count/fraction/cumulative)")
    p.add_argument("-g", "--grouping-metrics", default=None,
                   help="optional TSV of UMI grouping metrics (fgbio's "
                        "5-column UmiGroupingMetric)")
    p.add_argument("-M", "--metrics", default=None, metavar="PREFIX",
                   help="write PREFIX.family_sizes.txt, "
                        "PREFIX.grouping_metrics.txt and "
                        "PREFIX.position_group_sizes.txt")
    p.add_argument("--family-size-out", default=None,
                   help="deprecated: plain size/count TSV (use "
                        "--family-size-histogram)")
    p.add_argument("--threads", type=int, default=0,
                   help="reader/writer threads around the batch engine "
                        "(0/1 = inline)")
    p.add_argument("--stats", action="store_true",
                   help="print per-stage pipeline timing table")
    p.add_argument("--classic", action="store_true",
                   help="force the per-template engine (no batch vectorization)")
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_group)


def cmd_group(args, source=None, sink=None):
    from .commands.group import run_group
    from .io.bam import BamHeader, BamReader, BamWriter

    from .core.template import is_query_grouped, is_template_coordinate_sorted

    from .native import batch as nbat

    if getattr(args, "index_threshold", None) is not None:
        from .umi.assigners import set_index_threshold

        set_index_threshold(args.index_threshold)
    use_fast = nbat.available() and not getattr(args, "classic", False)
    if source is not None and not use_fast:
        log.error("group: fused chain requires the native batch engine")
        return 2
    t0 = time.monotonic()
    if source is not None:
        reader = source
    elif use_fast:
        from .io.batch_reader import BamBatchReader

        reader = BamBatchReader(args.input)
    else:
        reader = BamReader(args.input)
    with reader:
        hdr_text = reader.header.text
        # classify_input_ordering (group.rs:470-500): template-coordinate, or
        # query-grouped under --allow-unmapped; anything else is unusable.
        if not is_template_coordinate_sorted(hdr_text):
            if not (args.allow_unmapped and is_query_grouped(hdr_text)):
                log.error(
                    "group requires template-coordinate sorted input (header must "
                    "advertise SS:template-coordinate); sort with "
                    "`fgumi-tpu sort --order template-coordinate` first. "
                    "--allow-unmapped additionally accepts query-grouped input "
                    "(GO:query / SO:queryname).")
                return 2
        out_header = BamHeader(text=hdr_text, ref_names=reader.header.ref_names,
                               ref_lengths=reader.header.ref_lengths)
        # the ValueError catch wraps the writer context (not the other way
        # around) so a mid-run failure exits through writer.__exit__ with
        # the exception in hand: the output is discarded/aborted, never
        # committed — in the fused chain a clean close here would hand the
        # downstream stage a valid-looking EOF of a truncated stream
        try:
            with (BamWriter(args.output, out_header) if sink is None
                  else sink(out_header)) as writer:
                if use_fast:
                    from .commands.fast_group import FastGrouper
                    from .umi.assigners import make_assigner

                    if args.no_umi and args.strategy == "paired":
                        raise ValueError(
                            "--no-umi cannot be combined with the paired "
                            "strategy")
                    from .pipeline import StageTimes, run_stages
                    from .utils.progress import ProgressTracker

                    stats_t = StageTimes()
                    progress = ProgressTracker("group")
                    grouper = FastGrouper(
                        reader.header, make_assigner(args.strategy, args.edits),
                        umi_tag=args.raw_tag.encode(),
                        assigned_tag=args.assign_tag.encode(),
                        min_mapq=args.min_map_q,
                        include_non_pf=args.include_non_pf_reads,
                        min_umi_length=args.min_umi_length,
                        no_umi=args.no_umi,
                        allow_unmapped=args.allow_unmapped)

                    def _process(batch):
                        progress.add(batch.n)
                        return grouper.process_batch(batch)

                    try:
                        run_stages(iter(reader), _process,
                                   writer.write_serialized,
                                   threads=args.threads, stats=stats_t,
                                   **_stage_kwargs(args))
                        for chunk in grouper.flush():
                            writer.write_serialized(chunk)
                    finally:
                        # failure reports still carry records.group
                        progress.finish()
                    result = grouper.result()
                    if getattr(args, "stats", False):
                        _print_stats(stats_t)
                else:
                    result = run_group(
                        reader, writer, strategy=args.strategy,
                        edits=args.edits, umi_tag=args.raw_tag.encode(),
                        assigned_tag=args.assign_tag.encode(),
                        min_mapq=args.min_map_q,
                        include_non_pf=args.include_non_pf_reads,
                        min_umi_length=args.min_umi_length,
                        no_umi=args.no_umi,
                        allow_unmapped=args.allow_unmapped)
        except ValueError as e:
            log.error("%s", e)
            return 2
    dt = time.monotonic() - t0
    log.info("group: wrote %d records in %.2fs; filter=%s", result["records_out"],
             dt, result["filter"])
    if args.family_size_out:
        from .commands.dedup import write_family_size_histogram

        write_family_size_histogram(result["family_sizes"],
                                    args.family_size_out)
    if (args.family_size_histogram or args.grouping_metrics or args.metrics):
        from .metrics import (size_distribution_fields,
                              size_distribution_rows,
                              umi_grouping_metrics_row, write_metrics)

        dist_fields = size_distribution_fields
        fam_rows = size_distribution_rows(result["family_sizes"],
                                          "family_size")
        group_row = [umi_grouping_metrics_row(result["filter"])]
        if args.family_size_histogram:
            write_metrics(args.family_size_histogram, fam_rows,
                          fieldnames=dist_fields("family_size"))
        if args.grouping_metrics:
            write_metrics(args.grouping_metrics, group_row)
        if args.metrics:
            write_metrics(args.metrics + ".family_sizes.txt", fam_rows,
                          fieldnames=dist_fields("family_size"))
            write_metrics(args.metrics + ".grouping_metrics.txt", group_row)
            write_metrics(
                args.metrics + ".position_group_sizes.txt",
                size_distribution_rows(result["position_group_sizes"],
                                       "position_group_size"),
                fieldnames=dist_fields("position_group_size"))
    return 0


def _add_sort(sub):
    p = sub.add_parser("sort", help="Sort a BAM (coordinate/queryname/template-coordinate)")
    p.add_argument("-i", "--input", required=True)
    p.add_argument("-o", "--output", default=None,
                   help="output BAM (not needed with --verify)")
    p.add_argument("--verify", nargs="?", const=True, default=False,
                   type=_parse_bool,
                   help="verify the input satisfies --order (no output "
                        "written); exits non-zero on the first out-of-order "
                        "record")
    p.add_argument("--sort-threads", type=int, default=None,
                   help="threads for the sort/spill phase (defaults to "
                        "--threads; scheduling only, output byte-identical)")
    p.add_argument("--merge-threads", type=int, default=None,
                   help="threads for the merge/output phase (defaults to "
                        "--threads; scheduling only, output byte-identical)")
    p.add_argument("--max-temp-files", type=int, default=None,
                   help="advisory cap on spill runs (the k-way merge here "
                        "streams any run count; values < 2 are rejected)")
    p.add_argument("--temp-codec", default="deflate",
                   help="spill codec: deflate (libdeflate). zstd is not "
                        "available in this build and is rejected loudly")
    p.add_argument("--temp-compression", type=int, default=1,
                   help="accepted for compatibility (0-9 validated): spill "
                        "frames here always use deflate level 1, the "
                        "measured throughput/size sweet spot for "
                        "merge-once temporaries")
    p.add_argument("--key-types", default="full",
                   help="sort-key lanes: full (default; library+MI lanes, "
                        "the layout this engine always builds). Lane "
                        "subsetting is not supported here — any other value "
                        "is rejected loudly rather than silently changing "
                        "grouping semantics")
    p.add_argument("--threads", type=int, default=0,
                   help="N > 1 runs N-1 background spill workers: Phase-1 "
                        "sort/compress/write overlaps ingest "
                        "(worker_pool.rs analog; needs real cores to help)")
    p.add_argument("--order", default="template-coordinate",
                   choices=["coordinate", "queryname", "template-coordinate"])
    p.add_argument("--subsort", default="natural", choices=["natural", "lex"],
                   help="queryname comparator")
    p.add_argument("--max-memory", default="auto",
                   help="sort accumulation budget: MiB count, human size "
                        "(512M, 2G), or auto (cgroup-aware available minus "
                        "reserve)")
    p.add_argument("--memory-reserve", default="1G",
                   help="held back from auto-detected memory")
    p.add_argument("--max-records-in-ram", type=int, default=None,
                   help="optional additional record-count cap on the in-RAM "
                        "chunk (the primary budget is --max-memory bytes)")
    p.add_argument("--tmp-dir", default=None)
    p.add_argument("--write-index", type=_parse_bool, nargs="?", const=True,
                   default=True, metavar="true|false",
                   help="write an index alongside coordinate-sorted output")
    p.add_argument("--index-format", default="bai", choices=["bai", "csi"],
                   help="index flavor (csi handles references > 512 Mbp)")
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_sort)


def _rewrite_hd(text, so, go, ss):
    lines = text.splitlines()
    fields = {"VN": "1.6"}
    rest = []
    for line in lines:
        if line.startswith("@HD"):
            fields.update(f.split(":", 1) for f in line.split("\t")[1:] if ":" in f)
        else:
            rest.append(line)
    fields["SO"] = so
    fields.pop("GO", None)
    fields.pop("SS", None)
    if go:
        fields["GO"] = go
    if ss:
        fields["SS"] = ss
    hd = "@HD\t" + "\t".join(f"{k}:{v}" for k, v in fields.items())
    return "\n".join([hd] + rest) + "\n"


def cmd_sort(args, source=None, sink=None):
    from .io.bam import FLAG_UNMAPPED, BamHeader, BamReader, BamWriter, RawRecord
    from .sort.external import header_tags_for_order
    from .sort.keys import make_key_bytes_fn
    from .utils.memory import resolve_budget

    from .utils.memory import parse_size

    if args.key_types.strip().lower() not in ("full", "library,mi",
                                              "library mi", "mi,library"):
        log.error("--key-types %s: this engine always builds the full "
                  "library+MI key layout; lane subsetting would silently "
                  "change grouping semantics and is not supported",
                  args.key_types)
        return 2
    if args.temp_codec.strip().lower() not in ("deflate", "libdeflate"):
        log.error("--temp-codec %s: only deflate (libdeflate) is available "
                  "in this build (zstd is not in the image)", args.temp_codec)
        return 2
    if not 0 <= args.temp_compression <= 9:
        log.error("--temp-compression must be 0-9")
        return 2
    if args.max_temp_files is not None and args.max_temp_files < 2:
        log.error("--max-temp-files must be >= 2")
        return 2
    if args.verify:
        # verify-only mode (sort.rs:207-212): key monotonicity against the
        # REQUESTED --order over the packed byte keys, no output written
        with BamReader(args.input) as reader:
            key_fn = make_key_bytes_fn(args.order, reader.header,
                                       args.subsort)
            prev = b""
            for i, rec in enumerate(reader):
                k = key_fn(rec)
                if k < prev:
                    log.error("sort --verify: record %d out of %s order",
                              i, args.order)
                    return 1
                prev = k
        log.info("sort --verify: input satisfies %s order", args.order)
        return 0
    if args.output is None:
        log.error("-o/--output is required (unless --verify)")
        return 2
    if args.sort_threads is not None or args.merge_threads is not None:
        # scheduling-only knobs: this engine's worker pool serves both
        # phases, so the wider of the two sizes it
        args.threads = max(args.threads, args.sort_threads or 0,
                           args.merge_threads or 0)
    try:
        budget = resolve_budget(args.max_memory, parse_size(args.memory_reserve))
    except ValueError as e:
        log.error("%s", e)
        return 2
    if source is not None:
        return _cmd_sort_chain(args, source, sink, budget)
    t0 = time.monotonic()
    with BamReader(args.input) as reader:
        key_fn = make_key_bytes_fn(args.order, reader.header, args.subsort)
        so, go, ss = header_tags_for_order(args.order, args.subsort)
        out_header = BamHeader(
            text=_rewrite_hd(reader.header.text, so, go, ss),
            ref_names=reader.header.ref_names, ref_lengths=reader.header.ref_lengths)
        bai = None
        if args.order == "coordinate" and args.write_index:
            from .io.bai import BaiBuilder, CsiBuilder, depth_for_length

            if args.index_format == "csi":
                # depth sized to the longest reference (htslib rule) so
                # >512 Mbp chromosomes get valid bins
                bai = CsiBuilder(
                    len(reader.header.ref_names),
                    depth=depth_for_length(
                        max(reader.header.ref_lengths, default=0)))
            else:
                bai = BaiBuilder(len(reader.header.ref_names))
        from .utils.progress import ProgressTracker

        progress = ProgressTracker("sort")
        wprogress = ProgressTracker("sort-write")
        from .sort.keys import make_batch_keys_fn

        batch_keys_fn = make_batch_keys_fn(args.order, reader.header,
                                           args.subsort)
        from .sort.external import NativeExternalSorter, create_sorter

        # --threads N > 1: N-1 background spill workers overlap Phase-1
        # sort/compress/write with ingest (worker_pool.rs analog)
        spill_workers = max(getattr(args, "threads", 0) - 1, 0)
        with create_sorter(key_fn, max_bytes=budget, tmp_dir=args.tmp_dir,
                           max_records=args.max_records_in_ram,
                           spill_workers=spill_workers) as sorter:
            if isinstance(sorter, NativeExternalSorter) \
                    and batch_keys_fn is not None:
                # whole-batch path: native key extraction + two pool memcpys
                # per batch, native sort/spill/merge
                from .io.batch_reader import BamBatchReader

                with BamBatchReader(args.input) as br:
                    for b in br:
                        sorter.add_record_batch(b, batch_keys_fn)
                        progress.add(b.n)
            elif batch_keys_fn is not None:
                from .sort.keys import iter_keyed_records

                add_entry = sorter.add_entry
                for key, data in iter_keyed_records(args.input, batch_keys_fn,
                                                    progress.add):
                    add_entry(key, data)
            else:
                for rec in reader:
                    sorter.add(rec)
                    progress.add()
            progress.finish()
            with BamWriter(args.output, out_header) as writer:
                if bai is None and isinstance(sorter, NativeExternalSorter):
                    for blob, lens in sorter.sorted_chunks_with_lens():
                        writer.write_serialized(blob)
                        wprogress.add(len(lens))
                elif bai is None:
                    for data in sorter.sorted_records():
                        writer.write_record_bytes(data)
                        wprogress.add()
                elif isinstance(sorter, NativeExternalSorter):
                    # indexed blob path: one multi-block write per chunk,
                    # virtual offsets reconstructed from the block table,
                    # record geometry decoded natively
                    import numpy as np

                    from .native import batch as nbat

                    for blob, lens in sorter.sorted_chunks_with_lens():
                        starts = np.zeros(len(lens) + 1, dtype=np.int64)
                        np.cumsum(lens, out=starts[1:])
                        voffs = writer.write_indexed(blob, starts)
                        buf = np.frombuffer(blob, dtype=np.uint8)
                        f = nbat.decode_fields(buf, starts[:-1])
                        cigar_off = (f["data_off"] + 32
                                     + f["l_read_name"].astype(np.int64))
                        ends = nbat.ref_spans(buf, cigar_off, f["n_cigar"],
                                              f["pos"])
                        bai.add_many(
                            f["ref_id"], f["pos"], ends, voffs[:-1],
                            voffs[1:], (f["flag"] & FLAG_UNMAPPED) == 0)
                        wprogress.add(len(lens))
                else:
                    for data in sorter.sorted_records():
                        rec = RawRecord(data)
                        vo0 = writer.tell_virtual()
                        writer.write_record_bytes(data)
                        wprogress.add()
                        bai.add(rec.ref_id, rec.pos,
                                rec.pos + max(rec.reference_length(), 1),
                                vo0, writer.tell_virtual(),
                                not rec.flag & FLAG_UNMAPPED)
            wprogress.finish()
        if bai is not None:
            bai.write(args.output + "." + args.index_format)
    dt = time.monotonic() - t0
    log.info("sort: %d records (%s, budget %dMB) in %.2fs (%.0f rec/s)",
             sorter.n_records, args.order, budget >> 20, dt,
             sorter.n_records / dt if dt else 0)
    return 0


def _cmd_sort_chain(args, source, sink, budget):
    """Channel-fed sort stage for the fused pipeline: ingest RecordBatches
    from `source` as the upstream stage produces them (Phase-1 spill
    workers overlap the producer), k-way merge, stream sorted wire chunks
    into `sink`. Native engine only — the fused chain is gated on native
    availability, so the pure-Python fallback never lands here."""
    from .io.bam import BamHeader
    from .sort.external import (NativeExternalSorter, create_sorter,
                                header_tags_for_order)
    from .sort.keys import make_batch_keys_fn, make_key_bytes_fn
    from .utils.progress import ProgressTracker

    t0 = time.monotonic()
    with source:
        in_header = source.header
        batch_keys_fn = make_batch_keys_fn(args.order, in_header,
                                           args.subsort)
        key_fn = make_key_bytes_fn(args.order, in_header, args.subsort)
        if batch_keys_fn is None:
            log.error("sort: fused chain requires the native batch engine")
            return 2
        so, go, ss = header_tags_for_order(args.order, args.subsort)
        out_header = BamHeader(
            text=_rewrite_hd(in_header.text, so, go, ss),
            ref_names=in_header.ref_names,
            ref_lengths=in_header.ref_lengths)
        progress = ProgressTracker("sort")
        spill_workers = max(getattr(args, "threads", 0) - 1, 0)
        with create_sorter(key_fn, max_bytes=budget, tmp_dir=args.tmp_dir,
                           max_records=args.max_records_in_ram,
                           spill_workers=spill_workers) as sorter:
            if not isinstance(sorter, NativeExternalSorter):
                log.error("sort: fused chain requires the native sorter")
                return 2
            from .observe.trace import span, spanned_iter

            with span("sort.ingest", rusage=True):
                sorter.ingest_batches(iter(source), batch_keys_fn,
                                      progress.add)
            progress.finish()
            wprogress = ProgressTracker("sort-write")
            with sink(out_header) as writer:
                for arr in spanned_iter("sort.merge",
                                        sorter.iter_sorted_wire(),
                                        rusage=True):
                    writer.write_serialized(arr)
                    wprogress.add()
            wprogress.finish()
    dt = time.monotonic() - t0
    log.info("sort: %d records (%s, budget %dMB) in %.2fs (%.0f rec/s)",
             sorter.n_records, args.order, budget >> 20, dt,
             sorter.n_records / dt if dt else 0)
    return 0


def _add_merge(sub):
    p = sub.add_parser("merge", help="Merge same-order sorted BAMs")
    p.add_argument("-i", "--input", nargs="+", default=[])
    p.add_argument("--input-list", default=None,
                   help="file with one input BAM path per line (combined "
                        "with -i)")
    p.add_argument("-o", "--output", required=True)
    p.add_argument("--order", default="template-coordinate",
                   choices=["coordinate", "queryname", "template-coordinate"])
    p.add_argument("--subsort", default="natural", choices=["natural", "lex"])
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_merge)


def cmd_merge(args):
    from .io.bam import BamHeader, BamReader, BamWriter
    from .sort.external import header_tags_for_order, make_key_fn, merge_sorted

    from .core.template import _hd_fields

    if args.input_list:
        try:
            with open(args.input_list) as f:
                stripped = (line.strip() for line in f)
                args.input = list(args.input) + [
                    s for s in stripped if s and not s.startswith("#")]
        except OSError as e:
            log.error("cannot read --input-list %s: %s", args.input_list, e)
            return 2
        missing = [p for p in args.input if not os.path.exists(p)]
        if missing:
            log.error("--input-list names missing file(s): %s",
                      ", ".join(missing[:5]))
            return 2
    if not args.input:
        log.error("no inputs: pass -i and/or --input-list")
        return 2
    readers = [BamReader(path) for path in args.input]
    try:
        first = readers[0].header
        so, go, ss = header_tags_for_order(args.order, args.subsort)
        for path, r in zip(args.input, readers):
            if (r.header.ref_names != first.ref_names
                    or r.header.ref_lengths != first.ref_lengths):
                log.error("merge: inputs have differing reference sequences")
                return 2
            hd = _hd_fields(r.header.text)
            ok = (hd.get("SO") == so and (go is None or hd.get("GO") == go)
                  and (ss is None or hd.get("SS") == ss))
            if not ok:
                log.error("merge: %s is not sorted by the requested order "
                          "(--order %s needs SO:%s%s%s; header has %s)",
                          path, args.order, so,
                          f" GO:{go}" if go else "", f" SS:{ss}" if ss else "", hd)
                return 2
        # union the @RG/@PG/@CO lines across all inputs (first occurrence wins)
        seen_lines = []
        seen_set = set()
        for r in readers:
            for line in r.header.text.splitlines():
                if line.startswith(("@RG", "@PG", "@CO")) and line not in seen_set:
                    seen_set.add(line)
                    seen_lines.append(line)
        base_lines = [l for l in first.text.splitlines()
                      if not l.startswith(("@RG", "@PG", "@CO"))]
        merged_text = "\n".join(base_lines + seen_lines) + "\n"
        out_header = BamHeader(text=_rewrite_hd(merged_text, so, go, ss),
                               ref_names=first.ref_names, ref_lengths=first.ref_lengths)
        from .sort.keys import make_batch_keys_fn

        batch_keys_fn = make_batch_keys_fn(args.order, first, args.subsort)
        n = 0
        with BamWriter(args.output, out_header) as writer:
            if batch_keys_fn is not None:
                # native path: packed byte keys extracted per batch; memcmp
                # order == semantic order, so heapq merges the byte keys.
                # The header-validation readers close first (the batch
                # readers re-open each path).
                import heapq

                from .sort.keys import iter_keyed_records

                for r in readers:
                    r.close()
                streams = [
                    ((key, idx, data)
                     for key, data in iter_keyed_records(p, batch_keys_fn))
                    for idx, p in enumerate(args.input)]
                for _, _, data in heapq.merge(*streams):
                    writer.write_record_bytes(data)
                    n += 1
            else:
                key_fn = make_key_fn(args.order, first, args.subsort)
                for data in merge_sorted(readers, key_fn):
                    writer.write_record_bytes(data)
                    n += 1
    finally:
        for r in readers:
            r.close()
    log.info("merge: %d records from %d inputs", n, len(args.input))
    return 0


def _add_fastq(sub):
    def _flags(s):
        return int(s, 16) if s.lower().startswith("0x") else int(s)

    p = sub.add_parser("fastq", help="BAM -> mate-paired interleaved FASTQ")
    p.add_argument("-i", "--input", required=True)
    p.add_argument("-o", "--output", default="-", help="output FASTQ (- for stdout)")
    p.add_argument("-n", "--no-read-suffix", nargs="?", const=True,
                   default=False, type=_parse_bool,
                   help="don't append /1 and /2 to read names")
    p.add_argument("-F", "--exclude-flags", type=_flags, default=0x900,
                   help="exclude reads with ANY of these flags "
                        "(default 0x900 = secondary|supplementary)")
    p.add_argument("-f", "--require-flags", type=_flags, default=0,
                   help="only include reads with ALL of these flags")
    p.add_argument("-a", "-U", "--annotate-read-names", nargs="?", const=True,
                   default=False, type=_parse_bool,
                   help="append the UMI to the read name before any /1 "
                        "suffix (samtools fastq -U / DRAGEN layout)")
    p.add_argument("--umi-tag", default="RX,OX",
                   help="comma list of tags to read the UMI from, first "
                        "present wins")
    p.add_argument("--umi-name-delim", default=":",
                   help="delimiter between read name and UMI")
    p.add_argument("--umi-sep", default="+",
                   help="duplex-UMI half separator in the read name "
                        "(stored '-' is rewritten to this)")
    p.add_argument("-K", "--bwa-chunk-size", type=int, default=150000000,
                   help="accepted for compatibility (bwa -K output buffer "
                        "sizing hint)")
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_fastq)


def cmd_fastq(args):
    from .constants import reverse_complement_bytes
    from .io.bam import BamReader, FLAG_FIRST, FLAG_REVERSE

    from .io.bam import FLAG_LAST, FLAG_PAIRED

    from .utils.atomic import discard_output, open_output

    out = sys.stdout.buffer if args.output == "-" else open_output(args.output)
    n = 0
    umi_tags = [t.strip().encode() for t in args.umi_tag.split(",")
                if t.strip()]
    name_delim = args.umi_name_delim.encode()
    umi_sep = args.umi_sep.encode()
    exclude = args.exclude_flags
    require = args.require_flags

    def umi_of(rec):
        for tag in umi_tags:
            v = rec.get_str(tag)
            if v:
                # stored duplex UMIs use '-' between halves; aligner-facing
                # names use --umi-sep (DRAGEN/samtools '+')
                return v.replace("-", umi_sep.decode()).encode()
        return None

    def emit(rec):
        nonlocal n
        seq = rec.seq_bytes()
        quals = rec.quals()
        if rec.flag & FLAG_REVERSE:
            seq = reverse_complement_bytes(seq)
            quals = quals[::-1]
        name = rec.name
        if args.annotate_read_names:
            umi = umi_of(rec)
            if umi:
                name = name + name_delim + umi
        suffix = b""
        if not args.no_read_suffix:
            suffix = b"/1" if rec.flag & FLAG_FIRST else (
                b"/2" if rec.flag & FLAG_LAST else b"")
        out.write(b"@" + name + suffix + b"\n" + seq + b"\n+\n"
                  + (quals + 33).tobytes() + b"\n")
        n += 1

    # R1/R2 are interleaved adjacently by buffering each read until its mate
    # arrives (mates may be far apart in coordinate-sorted input)
    from .io.bam import FLAG_SECONDARY, FLAG_SUPPLEMENTARY

    pending = {}
    try:
        with BamReader(args.input) as reader:
            for rec in reader:
                if (rec.flag & exclude) or (rec.flag & require) != require:
                    continue
                if rec.flag & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY):
                    # a non-default -F may admit secondary/supplementary
                    # records: they are emitted verbatim but NEVER enter the
                    # name-keyed mate pairing (a supplementary R1 would
                    # otherwise pair with its own primary and corrupt the
                    # interleaving)
                    emit(rec)
                    continue
                if not rec.flag & FLAG_PAIRED:
                    emit(rec)
                    continue
                mate = pending.pop(rec.name, None)
                if mate is None:
                    pending[rec.name] = rec
                else:
                    r1, r2 = (rec, mate) if rec.flag & FLAG_FIRST else (mate, rec)
                    emit(r1)
                    emit(r2)
        for rec in pending.values():  # orphaned mates, in input order
            emit(rec)
    except BaseException:
        if out is not sys.stdout.buffer:
            discard_output(out)
        raise
    else:
        out.flush()
        if out is not sys.stdout.buffer:
            out.close()
    log.info("fastq: wrote %d reads", n)
    return 0


def _add_extract(sub):
    p = sub.add_parser("extract", help="Extract UMIs from FASTQ into unmapped BAM")
    p.add_argument("-i", "--input", required=True, nargs="+",
                   help="FASTQ file per sequencing read (R1 [R2 I1 I2 ...])")
    p.add_argument("-o", "--output", required=True, help="output unmapped BAM")
    p.add_argument("-r", "--read-structures", nargs="*", default=[],
                   help="one per FASTQ, e.g. 8M12S+T (default +T for 1-2 inputs)")
    p.add_argument("-q", "--store-umi-quals", action="store_true")
    p.add_argument("-C", "--store-cell-quals", action="store_true")
    p.add_argument("-Q", "--store-sample-barcode-qualities", action="store_true")
    p.add_argument("-n", "--extract-umis-from-read-names", action="store_true")
    p.add_argument("-a", "--annotate-read-names", action="store_true")
    p.add_argument("-s", "--single-tag", default=None)
    p.add_argument("--read-group-id", default="A")
    p.add_argument("--sample", required=True)
    p.add_argument("--library", required=True)
    p.add_argument("-b", "--barcode", default=None)
    p.add_argument("--platform", default="illumina")
    p.add_argument("--platform-unit", default=None)
    p.add_argument("--platform-model", default=None)
    p.add_argument("--sequencing-center", default=None)
    p.add_argument("--predicted-insert-size", type=int, default=None)
    p.add_argument("--description", default=None)
    p.add_argument("--run-date", default=None)
    p.add_argument("--comment", nargs="*", default=[])
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_extract)


def cmd_extract(args, sink=None):
    from .commands.extract import ExtractError, ExtractOptions, run_extract

    opts = ExtractOptions(
        read_structures=args.read_structures, sample=args.sample,
        library=args.library, read_group_id=args.read_group_id,
        store_umi_quals=args.store_umi_quals,
        store_cell_quals=args.store_cell_quals,
        store_sample_barcode_quals=args.store_sample_barcode_qualities,
        extract_umis_from_read_names=args.extract_umis_from_read_names,
        annotate_read_names=args.annotate_read_names,
        single_tag=args.single_tag, barcode=args.barcode,
        platform=args.platform, platform_unit=args.platform_unit,
        platform_model=args.platform_model,
        sequencing_center=args.sequencing_center,
        predicted_insert_size=args.predicted_insert_size,
        description=args.description, run_date=args.run_date,
        comments=args.comment, command_line=_cmdline())
    t0 = time.monotonic()
    try:
        n_records, n_sets = run_extract(args.input, args.output, opts,
                                        sink=sink)
    except (ValueError, OSError) as e:  # ExtractError, ReadStructureError, bad I/O
        log.error("%s", e)
        return 2
    dt = time.monotonic() - t0
    log.info("extract: %d read sets -> %d records in %.2fs (%.0f reads/s)",
             n_sets, n_records, dt, n_records / dt if dt else 0)
    return 0


def _parse_bool(v):
    if isinstance(v, bool):
        return v
    if v.lower() in ("true", "t", "yes", "1"):
        return True
    if v.lower() in ("false", "f", "no", "0"):
        return False
    raise argparse.ArgumentTypeError(f"expected true/false, got {v!r}")


def _header_with_pg(header, command_line):
    """Copy a header, appending an @PG record chained to the last one."""
    from .io.bam import BamHeader

    lines = header.text.splitlines()
    pg_ids = set()
    last_pg = None
    for line in lines:
        if line.startswith("@PG"):
            fields = dict(f.split(":", 1) for f in line.split("\t")[1:] if ":" in f)
            if "ID" in fields:
                pg_ids.add(fields["ID"])
                last_pg = fields["ID"]
    pg_id = "fgumi-tpu"
    n = 1
    while pg_id in pg_ids:
        pg_id = f"fgumi-tpu.{n}"
        n += 1
    pg = f"@PG\tID:{pg_id}\tPN:fgumi-tpu"
    if last_pg is not None:
        pg += f"\tPP:{last_pg}"
    pg += f"\tCL:{command_line}"
    return BamHeader(text="\n".join(lines + [pg]) + "\n",
                     ref_names=header.ref_names, ref_lengths=header.ref_lengths)


def _merge_zipper_headers(mapped, unmapped):
    """Mapped header plus @RG/@PG/@CO lines only the unmapped header carries
    (build_output_header, zipper.rs:232-278): the aligner often drops the @RG
    written by extract, which downstream library lookups need."""
    from .io.bam import BamHeader

    def ids(lines, kind):
        out = set()
        for line in lines:
            if line.startswith(kind):
                fields = dict(f.split(":", 1) for f in line.split("\t")[1:] if ":" in f)
                if "ID" in fields:
                    out.add(fields["ID"])
        return out

    mapped_lines = mapped.text.splitlines()
    extra = []
    for kind in ("@RG", "@PG"):
        have = ids(mapped_lines, kind)
        for line in unmapped.text.splitlines():
            if line.startswith(kind):
                fields = dict(f.split(":", 1) for f in line.split("\t")[1:] if ":" in f)
                if fields.get("ID") not in have:
                    extra.append(line)
    mapped_co = {l for l in mapped_lines if l.startswith("@CO")}
    extra.extend(l for l in unmapped.text.splitlines()
                 if l.startswith("@CO") and l not in mapped_co)
    if not extra:
        return mapped
    return BamHeader(text="\n".join(mapped_lines + extra) + "\n",
                     ref_names=mapped.ref_names, ref_lengths=mapped.ref_lengths)


def _add_zipper(sub):
    p = sub.add_parser("zipper", help="Zip unmapped BAM with aligned BAM")
    p.add_argument("-i", "--input", required=True,
                   help="mapped BAM from the aligner (queryname ordered)")
    p.add_argument("-u", "--unmapped", required=True,
                   help="unmapped BAM with tags to restore (same ordering)")
    p.add_argument("-o", "--output", required=True)
    p.add_argument("--tags-to-remove", nargs="*", default=[])
    p.add_argument("--tags-to-reverse", nargs="*", default=[],
                   help="tags (or the 'Consensus' set) to reverse on negative strand")
    p.add_argument("--tags-to-revcomp", nargs="*", default=[],
                   help="tags (or the 'Consensus' set) to revcomp on negative strand")
    p.add_argument("--skip-tc-tags", nargs="?", const=True, default=False,
                   type=_parse_bool)
    p.add_argument("--exclude-missing-reads", nargs="?", const=True,
                   default=False, type=_parse_bool,
                   help="drop unmapped-BAM reads the aligner omitted")
    p.add_argument("--restore-unconverted-bases", nargs="?", const=True,
                   default=False, type=_parse_bool,
                   help="EM-Seq: rewrite converted bases back to the "
                        "unconverted reference form at aligned ref-C/ref-G "
                        "positions after bwameth re-alignment (uses the "
                        "bwameth YD strand tag; requires --ref)")
    p.add_argument("-r", "--ref", default=None,
                   help="reference FASTA (required with "
                        "--restore-unconverted-bases)")
    p.add_argument("-K", "--bwa-chunk-size", type=int, default=150000000,
                   help="accepted for compatibility (bwa -K stdin buffer "
                        "sizing hint; this reader sizes buffers adaptively)")
    p.add_argument("--classic", action="store_true",
                   help="force the per-template engine (no batch "
                        "vectorization)")
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_zipper)


def cmd_zipper(args):
    from .commands.zipper import TagInfo, run_zipper
    from .core.template import is_query_grouped
    from .io.bam import BamReader, BamWriter

    tag_info = TagInfo.from_options(
        remove=args.tags_to_remove, reverse=args.tags_to_reverse,
        revcomp=args.tags_to_revcomp)
    from .native import batch as nbat

    restore = None
    if args.restore_unconverted_bases:
        if args.ref is None:
            log.error("--restore-unconverted-bases requires --ref")
            return 2
        from .core.reference import ReferenceReader

        try:
            restore_ref = ReferenceReader(args.ref)
        except OSError as e:
            log.error("cannot read reference %s: %s", args.ref, e)
            return 2
        with BamReader(args.input) as _r:
            restore = (restore_ref, _r.header.ref_names)
    # the batch engine's staged-append model cannot express static removal
    # of the tags it itself appends (MQ/MC/ms/AS/XS) -> classic engine
    # there; the EM-Seq restore also runs per record in the classic engine
    use_fast = (nbat.available() and not getattr(args, "classic", False)
                and restore is None
                and not (tag_info.remove & {"MQ", "MC", "ms", "AS", "XS"}))
    if nbat.available():
        from .io.batch_reader import BatchedRecordReader as _Reader
    else:
        _Reader = BamReader
    t0 = time.monotonic()
    try:
        if use_fast:
            from .commands.fast_zipper import run_zipper_fast
            from .io.batch_reader import BamBatchReader

            with BamBatchReader(args.input) as mapped, \
                    BamBatchReader(args.unmapped) as unmapped:
                for name, r in (("mapped", mapped), ("unmapped", unmapped)):
                    if not is_query_grouped(r.header.text):
                        log.error(
                            "zipper requires queryname-sorted or "
                            "query-grouped %s input (@HD must advertise "
                            "SO:queryname or GO:query)", name)
                        return 2
                out_header = _header_with_pg(
                    _merge_zipper_headers(mapped.header, unmapped.header),
                    _cmdline())
                with BamWriter(args.output, out_header) as writer:
                    n_templates, n_records, n_missing = run_zipper_fast(
                        mapped, unmapped, writer, tag_info,
                        skip_tc_tags=args.skip_tc_tags,
                        exclude_missing_reads=args.exclude_missing_reads)
        else:
            with _Reader(args.input) as mapped, \
                    _Reader(args.unmapped) as unmapped:
                for name, r in (("mapped", mapped), ("unmapped", unmapped)):
                    if not is_query_grouped(r.header.text):
                        log.error(
                            "zipper requires queryname-sorted or "
                            "query-grouped %s input (@HD must advertise "
                            "SO:queryname or GO:query)", name)
                        return 2
                out_header = _header_with_pg(
                    _merge_zipper_headers(mapped.header, unmapped.header),
                    _cmdline())
                with BamWriter(args.output, out_header) as writer:
                    n_templates, n_records, n_missing = run_zipper(
                        mapped, unmapped, writer, tag_info,
                        skip_tc_tags=args.skip_tc_tags,
                        exclude_missing_reads=args.exclude_missing_reads,
                        restore_unconverted=restore)
    except (ValueError, OSError) as e:
        log.error("%s", e)
        return 2
    dt = time.monotonic() - t0
    log.info("zipper: %d templates (%d records) in %.2fs (%.0f rec/s)",
             n_templates, n_records, dt, n_records / dt if dt else 0)
    if n_missing:
        verb = "excluded" if args.exclude_missing_reads else "passed through"
        log.info("zipper: %d templates not present in the aligned BAM (%s)",
                 n_missing, verb)
    return 0


def _add_filter(sub):
    p = sub.add_parser("filter", help="Filter and mask consensus reads")
    p.add_argument("-i", "--input", required=True,
                   help="consensus BAM (queryname sorted or query grouped)")
    p.add_argument("-o", "--output", required=True)
    p.add_argument("-M", "--min-reads", required=True,
                   help="1-3 comma-separated values [duplex,AB,BA]")
    p.add_argument("-E", "--max-read-error-rate", default="0.025",
                   help="1-3 comma-separated values")
    p.add_argument("-e", "--max-base-error-rate", default="0.1",
                   help="1-3 comma-separated values")
    p.add_argument("-N", "--min-base-quality", type=int, default=None)
    p.add_argument("-q", "--min-mean-base-quality", type=float, default=None)
    p.add_argument("-n", "--max-no-call-fraction", type=float, default=0.2,
                   help="<1.0: fraction of read length; >=1.0: absolute count")
    p.add_argument("-R", "--reverse-per-base-tags", nargs="?", const=True,
                   default=False, type=_parse_bool)
    p.add_argument("--filter-by-template", nargs="?", const=True,
                   default=True, type=_parse_bool)
    p.add_argument("-s", "--require-single-strand-agreement", nargs="?",
                   const=True, default=False, type=_parse_bool)
    p.add_argument("--min-methylation-depth", default=None,
                   help="EM-Seq/TAPS: mask bases whose methylation evidence "
                        "(cu+ct) is below this; 1-3 comma values "
                        "[duplex,AB,BA] (duplex also checks au+at / bu+bt)")
    p.add_argument("--require-strand-methylation-agreement", nargs="?",
                   const=True, default=False, type=_parse_bool,
                   help="mask both positions of a CpG when top/bottom strand "
                        "methylation calls disagree (duplex; requires --ref)")
    p.add_argument("--min-conversion-fraction", type=float, default=None,
                   help="reject reads whose conversion fraction at non-CpG "
                        "ref-C positions is below this (requires --ref and "
                        "--methylation-mode)")
    p.add_argument("--methylation-mode", choices=["em-seq", "taps"],
                   default=None,
                   help="numerator convention for --min-conversion-fraction "
                        "(em-seq: converted, taps: unconverted)")
    p.add_argument("--rejects", default=None, help="BAM for rejected reads")
    p.add_argument("-r", "--ref", default=None,
                   help="reference FASTA: regenerate NM/UQ/MD after masking "
                        "(required for mapped input)")
    p.add_argument("--classic", action="store_true",
                   help="force the per-record engine (no batch vectorization)")
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_filter)


def cmd_filter(args, source=None):
    from .commands.filter import run_filter
    from .consensus.filter import FilterConfig
    from .io.bam import BamReader, BamWriter

    if args.min_conversion_fraction is not None and not args.methylation_mode:
        log.error("--min-conversion-fraction requires --methylation-mode")
        return 2
    if (args.require_strand_methylation_agreement
            or args.min_conversion_fraction is not None) and not args.ref:
        log.error("--require-strand-methylation-agreement and "
                  "--min-conversion-fraction require --ref")
        return 2
    try:
        config = FilterConfig.new(
            [int(v) for v in args.min_reads.split(",")],
            [float(v) for v in args.max_read_error_rate.split(",")],
            [float(v) for v in args.max_base_error_rate.split(",")],
            min_base_quality=args.min_base_quality,
            min_mean_base_quality=args.min_mean_base_quality,
            max_no_call_fraction=args.max_no_call_fraction,
            require_ss_agreement=args.require_single_strand_agreement,
            methylation_depth=(args.min_methylation_depth.split(",")
                               if args.min_methylation_depth else None),
            require_strand_methylation_agreement=(
                args.require_strand_methylation_agreement),
            min_conversion_fraction=args.min_conversion_fraction,
            methylation_mode=args.methylation_mode)
    except ValueError as e:
        log.error("%s", e)
        return 2
    from .native import batch as nbat

    use_fast = (nbat.available() and not args.ref
                and not args.reverse_per_base_tags
                and not args.require_single_strand_agreement
                and not getattr(args, "classic", False))
    if source is not None and not use_fast:
        log.error("filter: fused chain requires the native batch engine")
        return 2
    t0 = time.monotonic()
    try:
        reference = None
        if args.ref:
            from .core.reference import ReferenceReader
            reference = ReferenceReader(args.ref)

        _SORT_ERR = (
            "filter requires queryname-sorted or query-grouped input "
            "(@HD must advertise SO:queryname or GO:query); run "
            "`fgumi-tpu sort --order queryname` first")

        def classic_run():
            with BamReader(args.input) as reader:
                from .core.template import is_query_grouped
                if not is_query_grouped(reader.header.text):
                    return None
                out_header = _header_with_pg(reader.header,
                                             _cmdline())
                rejects = (BamWriter(args.rejects, out_header)
                           if args.rejects else None)
                ok = False
                try:
                    with BamWriter(args.output, out_header) as writer:
                        stats_ = run_filter(
                            reader, writer, config,
                            filter_by_template=args.filter_by_template,
                            reverse_per_base=args.reverse_per_base_tags,
                            rejects_writer=rejects, reference=reference)
                    ok = True
                    return stats_
                finally:
                    if rejects is not None:
                        (rejects.close if ok else rejects.discard)()

        stats = None
        if use_fast:
            from .commands.fast_filter import FastFilter, _OddSubtype
            from .io.batch_reader import BamBatchReader

            try:
                with (BamBatchReader(args.input) if source is None
                      else source) as reader:
                    from .core.template import is_query_grouped
                    # Template filtering needs mates adjacent
                    # (filter.rs:343-349 require_query_grouped).
                    if not is_query_grouped(reader.header.text):
                        log.error("%s", _SORT_ERR)
                        return 2
                    out_header = _header_with_pg(reader.header,
                                                 _cmdline())
                    rejects = (BamWriter(args.rejects, out_header)
                               if args.rejects else None)
                    ok = False
                    try:
                        with BamWriter(args.output, out_header) as writer:
                            ff = FastFilter(
                                config,
                                filter_by_template=args.filter_by_template)
                            emit_rej = (rejects.write_serialized
                                        if rejects else None)
                            for batch in reader:
                                ff.process_batch(
                                    batch, writer.write_serialized, emit_rej)
                            ff.flush(writer.write_serialized, emit_rej)
                            stats = ff.stats
                        ok = True
                    finally:
                        if rejects is not None:
                            (rejects.close if ok else rejects.discard)()
            except _OddSubtype:
                if source is not None:
                    # a channel cannot be re-read; the fused driver gates on
                    # the standard consensus tag surface so this is a bug,
                    # not a user-reachable state
                    log.error("filter: unexpected per-base tag subtype on a "
                              "fused stream (cannot re-run classic)")
                    return 2
                log.info("filter: unexpected per-base tag subtype; "
                         "re-running with the classic engine")
                stats = None
        if stats is None:
            stats = classic_run()
            if stats is None:
                log.error("%s", _SORT_ERR)
                return 2
    except (ValueError, OSError, KeyError) as e:
        log.error("%s", e)
        return 2
    dt = time.monotonic() - t0
    log.info("filter: %d records -> kept %d, rejected %d, masked %d bases "
             "in %.2fs", stats.total_records, stats.passed_records,
             stats.failed_records, stats.bases_masked, dt)
    if stats.rejection_reasons:
        log.info("rejections: %s", dict(stats.rejection_reasons.most_common()))
    return 0


def _add_downsample(sub):
    p = sub.add_parser("downsample", help="Downsample BAM by UMI family")
    p.add_argument("-i", "--input", required=True,
                   help="grouped BAM with MI tags (template-coordinate order)")
    p.add_argument("-o", "--output", required=True)
    p.add_argument("-f", "--fraction", type=float, required=True,
                   help="fraction of UMI families to keep, in (0.0, 1.0]")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--rejects", default=None)
    p.add_argument("--validate-mi-order", nargs="?", const=True,
                   default=True, type=_parse_bool)
    p.add_argument("--histogram-kept", default=None)
    p.add_argument("--histogram-rejected", default=None)
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_downsample)


def cmd_downsample(args):
    from .commands.downsample import run_downsample, write_histogram
    from .io.bam import BamReader, BamWriter

    t0 = time.monotonic()
    try:
        with BamReader(args.input) as reader:
            out_header = _header_with_pg(reader.header, _cmdline())
            rejects = (BamWriter(args.rejects, out_header)
                       if args.rejects else None)
            ok = False
            try:
                with BamWriter(args.output, out_header) as writer:
                    stats = run_downsample(
                        reader, writer, args.fraction, seed=args.seed,
                        rejects_writer=rejects,
                        validate_mi_order=args.validate_mi_order)
                ok = True
            finally:
                if rejects is not None:
                    (rejects.close if ok else rejects.discard)()
    except (ValueError, OSError) as e:
        log.error("%s", e)
        return 2
    if args.histogram_kept:
        write_histogram(stats.kept_sizes, args.histogram_kept)
    if args.histogram_rejected:
        write_histogram(stats.rejected_sizes, args.histogram_rejected)
    dt = time.monotonic() - t0
    log.info("downsample: kept %d/%d families (%d/%d records) in %.2fs",
             stats.families_kept, stats.families_total, stats.records_kept,
             stats.records_total, dt)
    return 0


def _add_simulate(sub):
    p = sub.add_parser("simulate", help="Generate synthetic test data")
    ps = p.add_subparsers(dest="sim_mode", required=True)
    g = ps.add_parser("grouped-reads", help="MI-grouped BAM (simplex input)")
    g.add_argument("-o", "--output", required=True)
    g.add_argument("--num-families", type=int, default=100)
    g.add_argument("--family-size", type=int, default=5)
    g.add_argument("--family-size-distribution", default="fixed",
                   choices=["fixed", "lognormal", "longtail"],
                   help="longtail = Pareto-tailed 1-50 mixture (BASELINE "
                        "eval config 2 shape)")
    g.add_argument("--read-length", type=int, default=100)
    g.add_argument("--read-length-jitter", type=int, default=0,
                   help="per-read 3' truncation up to N bases (ragged "
                        "consensus-length stress)")
    g.add_argument("--qual-slope", type=float, default=0.0,
                   help="per-position Phred decay along the read")
    g.add_argument("--insert-size-mean", type=int, default=None,
                   help="normal insert-size model (default: uniform "
                        "1.5-3x read length)")
    g.add_argument("--insert-size-sd", type=int, default=0)
    g.add_argument("--error-rate", type=float, default=0.01)
    g.add_argument("--base-quality", type=int, default=35)
    g.add_argument("--single-end", action="store_true")
    g.add_argument("--seed", type=int, default=42)
    g.set_defaults(func=cmd_simulate_grouped)
    d = ps.add_parser("duplex-reads", help="duplex-grouped BAM (/A,/B MI tags)")
    d.add_argument("-o", "--output", required=True)
    d.add_argument("--num-molecules", type=int, default=100)
    d.add_argument("--reads-per-strand", type=int, default=3)
    d.add_argument("--read-length", type=int, default=100)
    d.add_argument("--error-rate", type=float, default=0.01)
    d.add_argument("--base-quality", type=int, default=35)
    d.add_argument("--ba-fraction", type=float, default=1.0)
    d.add_argument("--strand-bias-alpha", type=float, default=None,
                   help="Beta(alpha, beta) A/B strand read split (PCR "
                        "amplification bias model); default: symmetric "
                        "fixed split")
    d.add_argument("--strand-bias-beta", type=float, default=None)
    d.add_argument("--seed", type=int, default=42)
    d.set_defaults(func=cmd_simulate_duplex)
    c = ps.add_parser("codec-reads", help="CODEC-shaped BAM (overlapping FR pairs, MI tags)")
    c.add_argument("-o", "--output", required=True)
    c.add_argument("--num-molecules", type=int, default=100)
    c.add_argument("--pairs-per-molecule", type=int, default=1)
    c.add_argument("--read-length", type=int, default=100)
    c.add_argument("--error-rate", type=float, default=0.01)
    c.add_argument("--base-quality", type=int, default=35)
    c.add_argument("--overlap-fraction", type=float, default=0.5)
    c.add_argument("--seed", type=int, default=42)
    c.set_defaults(func=cmd_simulate_codec)
    m = ps.add_parser("mapped-reads", help="template-coordinate BAM with RX tags (group input)")
    m.add_argument("-o", "--output", required=True)
    m.add_argument("--num-families", type=int, default=100)
    m.add_argument("--family-size", type=int, default=5)
    m.add_argument("--read-length", type=int, default=100)
    m.add_argument("--umi-length", type=int, default=8)
    m.add_argument("--umi-error-rate", type=float, default=0.02)
    m.add_argument("--paired-umis", action="store_true")
    m.add_argument("--seed", type=int, default=42)
    m.set_defaults(func=cmd_simulate_mapped)
    f = ps.add_parser("fastq-reads",
                      help="paired gzip FASTQ with UMI prefixes (extract input)")
    f.add_argument("-1", "--r1", required=True, dest="r1")
    f.add_argument("-2", "--r2", required=True, dest="r2")
    f.add_argument("--truth", default=None, help="truth TSV output")
    f.add_argument("--num-families", type=int, default=100)
    f.add_argument("--family-size", type=int, default=5)
    f.add_argument("--family-size-distribution", default="fixed",
                   choices=["fixed", "lognormal", "longtail"])
    f.add_argument("--read-length", type=int, default=100)
    f.add_argument("--umi-length", type=int, default=8)
    f.add_argument("--error-rate", type=float, default=0.0)
    f.add_argument("--base-quality", type=int, default=35)
    f.add_argument("--duplex", action="store_true",
                   help="UMI prefix on both reads (duplex extraction)")
    f.add_argument("--includelist", default=None,
                   help="sample UMIs from this file (one per line)")
    f.add_argument("--seed", type=int, default=42)
    f.set_defaults(func=cmd_simulate_fastq)
    cr = ps.add_parser("consensus-reads",
                       help="mapped BAM shaped like consensus output (filter input)")
    cr.add_argument("-o", "--output", required=True)
    cr.add_argument("--truth", default=None)
    cr.add_argument("-n", "--num-reads", type=int, default=1000)
    cr.add_argument("-l", "--read-length", type=int, default=150)
    cr.add_argument("--min-depth", type=int, default=1)
    cr.add_argument("--max-depth", type=int, default=10)
    cr.add_argument("--depth-mean", type=float, default=5.0)
    cr.add_argument("--depth-stddev", type=float, default=2.0)
    cr.add_argument("--error-rate-mean", type=float, default=0.01)
    cr.add_argument("--no-per-base-tags", action="store_true")
    cr.add_argument("--seed", type=int, default=42)
    cr.set_defaults(func=cmd_simulate_consensus)
    co = ps.add_parser("correct-reads",
                       help="unmapped BAM + UMI includelist (correct input)")
    co.add_argument("-o", "--output", required=True)
    co.add_argument("-i", "--includelist", required=True,
                    help="includelist file to write")
    co.add_argument("--truth", default=None)
    co.add_argument("-n", "--num-reads", type=int, default=10000)
    co.add_argument("--num-umis", type=int, default=1000)
    co.add_argument("-u", "--umi-length", type=int, default=8)
    co.add_argument("-l", "--read-length", type=int, default=100)
    co.add_argument("--max-errors", type=int, default=2)
    co.add_argument("--seed", type=int, default=42)
    co.set_defaults(func=cmd_simulate_correct)


def cmd_simulate_fastq(args):
    from .simulate import simulate_fastq_reads

    n = simulate_fastq_reads(
        args.r1, args.r2, truth_path=args.truth,
        num_families=args.num_families, family_size=args.family_size,
        family_size_distribution=args.family_size_distribution,
        read_length=args.read_length, umi_length=args.umi_length,
        error_rate=args.error_rate, base_quality=args.base_quality,
        duplex=args.duplex, includelist=args.includelist, seed=args.seed)
    log.info("simulate: wrote %d read pairs to %s / %s", n, args.r1, args.r2)
    return 0


def cmd_simulate_consensus(args):
    from .simulate import simulate_consensus_bam

    n = simulate_consensus_bam(
        args.output, truth_path=args.truth, num_reads=args.num_reads,
        read_length=args.read_length, min_depth=args.min_depth,
        max_depth=args.max_depth, depth_mean=args.depth_mean,
        depth_stddev=args.depth_stddev, error_rate_mean=args.error_rate_mean,
        per_base_tags=not args.no_per_base_tags, seed=args.seed)
    log.info("simulate: wrote %d consensus records to %s", n, args.output)
    return 0


def cmd_simulate_correct(args):
    from .simulate import simulate_correct_reads

    n = simulate_correct_reads(
        args.output, args.includelist, truth_path=args.truth,
        num_reads=args.num_reads, num_umis=args.num_umis,
        umi_length=args.umi_length, read_length=args.read_length,
        max_errors=args.max_errors, seed=args.seed)
    log.info("simulate: wrote %d reads to %s (includelist %s)", n,
             args.output, args.includelist)
    return 0


def cmd_simulate_grouped(args):
    from .simulate import simulate_grouped_bam

    n = simulate_grouped_bam(
        args.output, num_families=args.num_families, family_size=args.family_size,
        family_size_distribution=args.family_size_distribution,
        read_length=args.read_length, error_rate=args.error_rate,
        base_quality=args.base_quality, paired=not args.single_end,
        read_length_jitter=args.read_length_jitter,
        qual_slope=args.qual_slope,
        insert_size_mean=args.insert_size_mean,
        insert_size_sd=args.insert_size_sd, seed=args.seed)
    log.info("simulate: wrote %d records to %s", n, args.output)
    return 0


def cmd_simulate_duplex(args):
    from .simulate import simulate_duplex_bam

    if args.strand_bias_beta is not None and args.strand_bias_alpha is None:
        log.error("--strand-bias-beta requires --strand-bias-alpha")
        return 2
    for name, v in (("--strand-bias-alpha", args.strand_bias_alpha),
                    ("--strand-bias-beta", args.strand_bias_beta)):
        if v is not None and v <= 0:
            log.error("%s must be > 0 (Beta distribution parameter)", name)
            return 2
    n = simulate_duplex_bam(
        args.output, num_molecules=args.num_molecules,
        reads_per_strand=args.reads_per_strand, read_length=args.read_length,
        error_rate=args.error_rate, base_quality=args.base_quality,
        ba_fraction=args.ba_fraction, seed=args.seed,
        strand_bias_alpha=args.strand_bias_alpha,
        strand_bias_beta=args.strand_bias_beta)
    log.info("simulate: wrote %d records to %s", n, args.output)
    return 0


def cmd_simulate_codec(args):
    from .simulate import simulate_codec_bam

    n = simulate_codec_bam(
        args.output, num_molecules=args.num_molecules,
        pairs_per_molecule=args.pairs_per_molecule, read_length=args.read_length,
        error_rate=args.error_rate, base_quality=args.base_quality,
        overlap_fraction=args.overlap_fraction, seed=args.seed)
    log.info("simulate: wrote %d records to %s", n, args.output)
    return 0


def cmd_simulate_mapped(args):
    from .simulate import simulate_mapped_bam

    n = simulate_mapped_bam(
        args.output, num_families=args.num_families, family_size=args.family_size,
        read_length=args.read_length, umi_length=args.umi_length,
        umi_error_rate=args.umi_error_rate, paired_umis=args.paired_umis,
        seed=args.seed)
    log.info("simulate: wrote %d records to %s", n, args.output)
    return 0


def _add_clip(sub):
    p = sub.add_parser("clip", help="Clip overlapping reads in BAM files")
    p.add_argument("-i", "--input", required=True,
                   help="queryname sorted/grouped BAM")
    p.add_argument("-o", "--output", required=True)
    p.add_argument("-r", "--reference", required=True,
                   help="reference FASTA (for NM/UQ/MD regeneration)")
    p.add_argument("-c", "--clipping-mode", default="hard",
                   choices=["soft", "soft-with-mask", "hard"])
    p.add_argument("--clip-overlapping-reads", action="store_true")
    p.add_argument("--clip-bases-past-mate", "--clip-extending-past-mate",
                   dest="clip_extending_past_mate", action="store_true")
    p.add_argument("--read-one-five-prime", type=int, default=0)
    p.add_argument("--read-one-three-prime", type=int, default=0)
    p.add_argument("--read-two-five-prime", type=int, default=0)
    p.add_argument("--read-two-three-prime", type=int, default=0)
    p.add_argument("-H", "--upgrade-clipping", action="store_true",
                   help="upgrade existing clipping to the configured mode")
    p.add_argument("-a", "--auto-clip-attributes", action="store_true",
                   help="hard-clip per-base tags matching read length")
    p.add_argument("-m", "--metrics", default=None)
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_clip)


def cmd_clip(args):
    from .commands.clip import ClipParams, run_clip, write_clip_metrics
    from .core.reference import ReferenceReader
    from .core.template import is_query_grouped
    from .io.bam import BamReader, BamWriter

    params = ClipParams(
        clipping_mode=args.clipping_mode,
        clip_overlapping_reads=args.clip_overlapping_reads,
        clip_extending_past_mate=args.clip_extending_past_mate,
        read_one_five_prime=args.read_one_five_prime,
        read_one_three_prime=args.read_one_three_prime,
        read_two_five_prime=args.read_two_five_prime,
        read_two_three_prime=args.read_two_three_prime,
        upgrade_clipping=args.upgrade_clipping,
        auto_clip_attributes=args.auto_clip_attributes)
    if not params.any_clipping():
        log.error("At least one clipping option is required")
        return 2
    t0 = time.monotonic()
    try:
        reference = ReferenceReader(args.reference)
        with BamReader(args.input) as reader:
            if not is_query_grouped(reader.header.text):
                log.error("clip requires queryname sorted or query grouped "
                          "input (@HD must advertise SO:queryname or GO:query); "
                          "sort with `fgumi-tpu sort --order queryname` first")
                return 2
            out_header = _header_with_pg(reader.header, _cmdline())
            with BamWriter(args.output, out_header) as writer:
                metrics = run_clip(reader, writer, reference, params)
    except (ValueError, OSError, KeyError) as e:
        log.error("%s", e)
        return 2
    dt = time.monotonic() - t0
    log.info("clip: %d templates (%d overlap-clipped, %d extend-clipped) "
             "in %.2fs", metrics.templates, metrics.overlap_clipped,
             metrics.extend_clipped, dt)
    if args.metrics:
        write_clip_metrics(metrics, args.metrics)
    return 0


def _add_correct(sub):
    p = sub.add_parser("correct", help="Correct UMIs to a fixed whitelist")
    p.add_argument("-i", "--input", required=True)
    p.add_argument("-o", "--output", required=True)
    p.add_argument("-u", "--umis", nargs="*", default=[],
                   help="whitelist UMI sequences")
    p.add_argument("-U", "--umi-files", nargs="*", default=[],
                   help="files with one whitelist UMI per line")
    p.add_argument("-m", "--metrics", default=None, help="per-UMI metrics TSV")
    p.add_argument("-r", "--rejects", default=None,
                   help="BAM for records whose UMI could not be corrected")
    p.add_argument("--target", choices=["umi", "barcode"], default="umi",
                   help="umi: RX (original in OX); barcode: BC (original in ob)")
    p.add_argument("--max-mismatches", type=int, default=2)
    p.add_argument("--min-distance", type=int, default=2, dest="min_distance_diff")
    p.add_argument("--dont-store-original", action="store_true")
    p.add_argument("--cache-size", type=int, default=100_000)
    p.add_argument("--min-corrected", type=float, default=None,
                   help="fail if kept/total falls below this fraction")
    p.add_argument("--revcomp", action="store_true",
                   help="reverse-complement observed UMIs before matching")
    p.add_argument("--classic", action="store_true",
                   help="force the per-template engine (no batch "
                        "vectorization)")
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_correct)


def cmd_correct(args):
    from .commands.correct import (UmiMatcher, find_umi_pairs_within_distance,
                                   load_umi_sequences, run_correct,
                                   write_correction_metrics)
    from .io.bam import BamReader, BamWriter

    if args.min_corrected is not None and not 0.0 <= args.min_corrected <= 1.0:
        log.error("--min-corrected must be between 0 and 1")
        return 2
    try:
        umis, umi_length = load_umi_sequences(args.umis, args.umi_files)
    except (ValueError, OSError) as e:
        log.error("%s", e)
        return 2
    log.info("correct: loaded %d whitelist UMIs of length %d", len(umis), umi_length)
    # ambiguity warning (fgbio uses min_distance_diff - 1; 0 reports nothing)
    if args.min_distance_diff > 0:
        pairs = find_umi_pairs_within_distance(umis, args.min_distance_diff - 1)
        for u1, u2, d in pairs:
            log.warning("whitelist UMIs within min-distance-diff: %s <-> %s "
                        "(distance %d) — may be ambiguous and fail to match",
                        u1, u2, d)
    matcher = UmiMatcher(umis, args.max_mismatches, args.min_distance_diff,
                         args.cache_size)
    from .native import batch as nbat

    use_fast = nbat.available() and not getattr(args, "classic", False)
    t0 = time.monotonic()
    try:
        if use_fast:
            from .commands.fast_correct import run_correct_fast
            from .io.batch_reader import BamBatchReader

            _Reader, _run = BamBatchReader, run_correct_fast
        else:
            _Reader, _run = BamReader, run_correct
        with _Reader(args.input) as reader:
            out_header = _header_with_pg(reader.header, _cmdline())
            import contextlib
            with contextlib.ExitStack() as stack:
                writer = stack.enter_context(BamWriter(args.output, out_header))
                rejects_writer = None
                if args.rejects:
                    rejects_writer = stack.enter_context(
                        BamWriter(args.rejects, out_header))
                stats = _run(
                    reader, writer, matcher, umi_length, target=args.target,
                    revcomp=args.revcomp,
                    store_original=not args.dont_store_original,
                    rejects_writer=rejects_writer)
    except (ValueError, OSError) as e:
        log.error("%s", e)
        return 2
    dt = time.monotonic() - t0
    rejected = stats.missing_umis + stats.wrong_length + stats.mismatched
    total = stats.records_written + rejected
    log.info("correct: read %d records; kept %d, rejected %d "
             "(%d missing, %d wrong length, %d mismatched) in %.2fs",
             total, stats.records_written, rejected, stats.missing_umis,
             stats.wrong_length, stats.mismatched, dt)
    if stats.missing_umis or stats.wrong_length:
        log.error("%d records missing UMI attributes; %d had UMIs of "
                  "unexpected length", stats.missing_umis, stats.wrong_length)
    if args.metrics:
        write_correction_metrics(stats, umi_length, args.metrics)
    if args.min_corrected is not None and total:
        ratio = stats.records_written / total
        if ratio < args.min_corrected:
            log.error("Final ratio of reads kept / total was %.2f (minimum "
                      "%.2f); this could indicate a mismatch between library "
                      "preparation and the provided UMI whitelist",
                      ratio, args.min_corrected)
            return 1
    return 0


def _add_dedup(sub):
    p = sub.add_parser("dedup", help="Mark or remove PCR duplicates using UMIs")
    p.add_argument("-i", "--input", required=True,
                   help="template-coordinate sorted BAM (zipper + sort)")
    p.add_argument("-o", "--output", required=True)
    p.add_argument("--max-memory", default="auto",
                   help="pipeline working-set budget (MiB count, human "
                        "size, or auto): bytes-in-flight bound on queued "
                        "batches in threaded runs")
    p.add_argument("-m", "--metrics", default=None, help="dedup metrics TSV")
    p.add_argument("-H", "--family-size-histogram", default=None)
    p.add_argument("-r", "--remove-duplicates", action="store_true",
                   help="drop duplicates instead of setting the 0x400 flag")
    p.add_argument("-q", "--min-map-q", type=int, default=0)
    p.add_argument("-n", "--include-non-pf-reads", action="store_true")
    p.add_argument("--include-unmapped", action="store_true",
                   help="emit no-mapped-read templates untouched instead of dropping")
    p.add_argument("-s", "--strategy", default="adjacency",
                   choices=["identity", "edit", "adjacency", "paired"])
    p.add_argument("-e", "--edits", type=int, default=1)
    p.add_argument("-l", "--min-umi-length", type=int, default=None)
    p.add_argument("--no-umi", action="store_true",
                   help="dedup by position only, orientation-agnostic (Picard-like)")
    p.add_argument("--index-threshold", type=int, default=None,
                   help="minimum distinct UMIs per group before the indexed "
                        "candidate search replaces the dense pairwise scan; "
                        "0 = always dense")
    p.add_argument("--threads", type=int, default=0,
                   help="reader/writer threads around the batch engine "
                        "(0/1 = inline)")
    p.add_argument("--stats", action="store_true",
                   help="print per-stage pipeline timing table")
    p.add_argument("--classic", action="store_true",
                   help="force the per-template engine (no batch vectorization)")
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_dedup)


def cmd_dedup(args):
    from .commands.dedup import (run_dedup, write_family_size_histogram,
                                 write_metrics)
    from .core.template import is_template_coordinate_sorted
    from .io.bam import BamReader, BamWriter

    if getattr(args, "index_threshold", None) is not None:
        from .umi.assigners import set_index_threshold

        set_index_threshold(args.index_threshold)
    # argument-combination validation before the output file is touched
    if args.strategy == "paired" and args.no_umi:
        log.error("--no-umi cannot be used with --strategy paired")
        return 2
    if args.strategy == "paired" and args.min_umi_length is not None:
        log.error("Paired strategy cannot be used with --min-umi-length")
        return 2

    from .native import batch as nbat

    use_fast = nbat.available() and not getattr(args, "classic", False)
    t0 = time.monotonic()
    try:
        if use_fast:
            from .io.batch_reader import BamBatchReader

            reader = BamBatchReader(args.input)
        else:
            reader = BamReader(args.input)
        with reader:
            hdr_text = reader.header.text
            if not is_template_coordinate_sorted(hdr_text):
                log.error(
                    "dedup requires template-coordinate sorted input (header must "
                    "advertise SS:template-coordinate). Prepare with:\n"
                    "  fgumi-tpu zipper ... | fgumi-tpu sort --order template-coordinate")
                return 2
            out_header = _header_with_pg(reader.header, _cmdline())
            with BamWriter(args.output, out_header) as writer:
                if use_fast:
                    from .commands.fast_group import FastDedup
                    from .umi.assigners import make_assigner

                    strategy, edits = args.strategy, args.edits
                    if args.no_umi:
                        strategy, edits = "identity", 0
                    from .pipeline import StageTimes, run_stages
                    from .utils.progress import ProgressTracker

                    stats_t = StageTimes()
                    progress = ProgressTracker("dedup")
                    dd = FastDedup(
                        reader.header, make_assigner(strategy, edits),
                        min_mapq=args.min_map_q,
                        include_non_pf=args.include_non_pf_reads,
                        min_umi_length=args.min_umi_length,
                        no_umi=args.no_umi,
                        include_unmapped=args.include_unmapped,
                        remove_duplicates=args.remove_duplicates)

                    def _process(batch):
                        progress.add(batch.n)
                        return dd.process_batch(batch)

                    try:
                        run_stages(iter(reader), _process,
                                   writer.write_serialized,
                                   threads=args.threads, stats=stats_t,
                                   **_stage_kwargs(args))
                        for chunk in dd.flush():
                            writer.write_serialized(chunk)
                    finally:
                        # failure reports still carry records.dedup
                        progress.finish()
                    metrics, family_sizes = dd.result()
                    if getattr(args, "stats", False):
                        _print_stats(stats_t)
                else:
                    metrics, family_sizes = run_dedup(
                        reader, writer, strategy=args.strategy,
                        edits=args.edits, min_mapq=args.min_map_q,
                        include_non_pf=args.include_non_pf_reads,
                        min_umi_length=args.min_umi_length,
                        no_umi=args.no_umi,
                        include_unmapped=args.include_unmapped,
                        remove_duplicates=args.remove_duplicates)
    except (ValueError, OSError) as e:
        log.error("%s", e)
        return 2
    dt = time.monotonic() - t0
    log.info("dedup: %d templates (%d unique, %d duplicate, rate %.4f), "
             "%d reads in %.2fs",
             metrics.total_templates, metrics.unique_templates,
             metrics.duplicate_templates, metrics.duplicate_rate(),
             metrics.total_reads, dt)
    dropped = metrics.filter.as_dict()
    dropped.pop("total_templates", None)
    dropped.pop("accepted", None)
    if dropped:
        log.info("dedup: templates dropped by filtering: %s", dropped)
    if metrics.missing_tc_tag:
        log.warning("%d secondary/supplementary reads missing the tc tag "
                    "(run zipper before sort)", metrics.missing_tc_tag)
    if args.metrics:
        write_metrics(metrics, args.metrics)
    if args.family_size_histogram:
        write_family_size_histogram(family_sizes, args.family_size_histogram)
    return 0


def _add_pipeline(sub):
    p = sub.add_parser(
        "pipeline",
        help="FASTQ -> filtered consensus BAM: extract, sort, group, "
             "simplex, filter chained in one process")
    p.add_argument("-i", "--input", required=True, nargs="+",
                   help="FASTQ file per sequencing read (R1 [R2 ...])")
    p.add_argument("-r", "--read-structures", nargs="*", default=[],
                   help="one per FASTQ, e.g. 8M12S+T (default +T)")
    p.add_argument("-o", "--output", required=True,
                   help="filtered consensus BAM")
    p.add_argument("--sample", required=True)
    p.add_argument("--library", required=True)
    p.add_argument("-s", "--strategy", default="adjacency",
                   help="UMI assignment strategy (group -s)")
    p.add_argument("--consensus-min-reads", type=int, default=1,
                   help="simplex --min-reads")
    p.add_argument("--filter-min-reads", type=int, default=3,
                   help="filter --min-reads")
    p.add_argument("--threads", type=int, default=0,
                   help="stage threads, forwarded to every stage that "
                        "accepts them (sort spill workers, group, simplex)")
    p.add_argument("--keep-intermediates", default=None, metavar="DIR",
                   help="write stage outputs here and keep them (forces the "
                        "classic staged path; default without it: fused "
                        "in-memory chain, no intermediate files)")
    p.add_argument("--no-fuse", action="store_true",
                   help="run the classic staged path (intermediate BAMs in "
                        "a temp dir) instead of the fused in-memory chain; "
                        "output is byte-identical either way")
    p.add_argument("--device-filter", action="store_true",
                   help="fuse the filter stage INTO simplex (ISSUE 11): "
                        "consensus columns stay device-resident, verdicts "
                        "come from the fused mask kernel, and only "
                        "surviving records are fetched + serialized — "
                        "byte-identical records to the chained filter "
                        "stage")
    _add_shard_opts(p)
    _add_pipeline_compat(p)
    p.set_defaults(func=cmd_pipeline)


def _pipeline_stage_argvs(args, j):
    """The five stage argv lists of the FastqToConsensus chain, shared by
    the staged and fused drivers (identical argv in both modes, so flag
    handling and any argv-derived behavior cannot drift between them).
    ``j(name)`` maps an intermediate file name to its path — a real temp
    path in staged mode, an unused placeholder in fused mode."""
    thr = ["--threads", str(args.threads)] if args.threads else []
    lvl0 = ["--compression-level", "0"]
    # user-facing compat flags forward to every stage; the user's
    # --compression-level applies to the FINAL output only (intermediates
    # stay level 0 by design — they are deleted as soon as they are read)
    fwd = []
    if args.memory_per_thread:
        fwd += ["--memory-per-thread", args.memory_per_thread]
    out_lvl = ([] if args.compression_level is None
               else ["--compression-level", str(args.compression_level)])
    rs = (["-r"] + args.read_structures) if args.read_structures else []
    # scatter sub-job: the front stages (extract/sort/group) replicate the
    # full deterministic stream on every shard — identical MI assignment
    # and family ordinals everywhere — and the shard filter cuts the
    # stream down at the simplex stage, where families become independent
    shard_fwd = []
    if getattr(args, "shard", None):
        shard_fwd = ["--shard", args.shard, "--shard-by", args.shard_by]
        if args.shard_manifest:
            shard_fwd += ["--shard-manifest", args.shard_manifest]
    # --threads reaches every stage with threaded internals: sort's Phase-1
    # spill workers and group's reader/writer stages are deterministic
    # (byte-identical output), not just simplex
    stages = [
        ("extract", ["extract", "-i"] + args.input + rs +
         ["-o", j("unmapped.bam"), "--sample", args.sample,
          "--library", args.library] + lvl0 + fwd),
        ("sort", ["sort", "-i", j("unmapped.bam"), "-o", j("sorted.bam"),
                  "--order", "template-coordinate"] + lvl0 + thr + fwd),
        ("group", ["group", "-i", j("sorted.bam"), "-o", j("grouped.bam"),
                   "-s", args.strategy, "--allow-unmapped"] + lvl0 + thr
         + fwd),
    ]
    if getattr(args, "device_filter", False):
        # fused consensus→filter (ISSUE 11): the filter stage disappears —
        # simplex carries the filter thresholds, judges every read from
        # the device-resident columns, and writes the FINAL output
        stages.append(
            ("simplex", ["simplex", "-i", j("grouped.bam"),
                         "-o", args.output,
                         "--min-reads", str(args.consensus_min_reads),
                         "--allow-unmapped", "--device-filter",
                         "--filter-min-reads", str(args.filter_min_reads)]
             + shard_fwd + out_lvl + thr + fwd))
        return stages
    stages += [
        ("simplex", ["simplex", "-i", j("grouped.bam"), "-o", j("cons.bam"),
                     "--min-reads", str(args.consensus_min_reads),
                     "--allow-unmapped"] + shard_fwd + lvl0 + thr + fwd),
        ("filter", ["filter", "-i", j("cons.bam"), "-o", args.output,
                    "--min-reads", str(args.filter_min_reads)] + out_lvl
         + fwd),
    ]
    return stages


def cmd_pipeline(args):
    """FastqToConsensus best-practice chain in one process.

    The reference ships this as a Snakemake workflow over separate fgumi
    invocations (/root/reference/docs/FastqToConsensus-RnD.smk:1-40). Two
    in-process drivers, byte-identical outputs:

    - **fused** (default when the native engine is available): adjacent
      stages hand decoded record batches through bounded in-memory channels
      (``pipeline_chain``) — no intermediate files, no BGZF encode/decode
      between stages, and the stages genuinely overlap (extract feeds
      sort's Phase-1 spill ingest as it produces; the sort merge is the
      natural barrier; group ⇒ simplex ⇒ filter stream as one segment).
    - **staged** (``--no-fuse``, ``--keep-intermediates``, or no native
      runtime): each stage re-enters main() and writes a stored (level-0)
      intermediate BAM, deleted as soon as the next stage has consumed it.
    """
    from .native import batch as nbat

    fuse = (not args.no_fuse and args.keep_intermediates is None
            and nbat.available())
    if fuse:
        return _pipeline_fused(args)
    if not args.no_fuse and args.keep_intermediates is None:
        log.info("pipeline: native batch engine unavailable; running the "
                 "staged chain")
    return _pipeline_staged(args)


def _pipeline_fused(args):
    """The fused in-memory chain driver: one thread per stage, adjacent
    stages joined by byte-budgeted channels. Failure in any stage aborts
    the chain (channels cascade ``ChainAborted`` both ways); the first
    stage in chain order with a real error decides the exit code, exactly
    like the staged driver's first-failing-stage contract."""
    import threading as _threading

    from .observe import heartbeat as _hb
    from .observe.metrics import METRICS
    from .observe.scope import set_thread_prefix, spawn_thread
    from .observe.trace import span
    from .pipeline_chain import (ChainAborted, ChainChannel,
                                 ChannelBamWriter, ChannelBatchReader)

    stages = _pipeline_stage_argvs(args, lambda name: f"<fused:{name}>")
    # nested-stage flag travel, exactly like the staged driver's `pre`
    pre = ["--no-atomic-output"] if args.no_atomic_output else []
    if args.audit_output:
        pre.append("--audit-output")
    parser = build_parser()
    ns = {name: parser.parse_args(pre + argv) for name, argv in stages}

    dfilt = getattr(args, "device_filter", False)
    c1 = ChainChannel("extract.sort")
    c2 = ChainChannel("sort.group")
    c3 = ChainChannel("group.simplex")
    c4 = None if dfilt else ChainChannel("simplex.filter")
    chans = [c1, c2, c3] + ([] if dfilt else [c4])

    def _sink(chan):
        return lambda header: ChannelBamWriter(chan, header)

    # writable=False only where the consumer provably never writes its
    # batches (sort ingest memcpys into pools, group builds fresh records).
    # simplex (overlap correction) and filter (native in-place N/Q2
    # masking via apply_masks, which writes through the raw pointer and
    # would bypass numpy's read-only guard entirely) need writable input
    calls = {
        "extract": lambda a: cmd_extract(a, sink=_sink(c1)),
        "sort": lambda a: cmd_sort(
            a, source=ChannelBatchReader(c1, writable=False),
            sink=_sink(c2)),
        "group": lambda a: cmd_group(
            a, source=ChannelBatchReader(c2, writable=False),
            sink=_sink(c3)),
        # --device-filter: simplex fuses the filter and writes the final
        # output itself (sink=None -> the ordinary BamWriter)
        "simplex": lambda a: cmd_simplex(
            a, source=ChannelBatchReader(
                c3, target_bytes=ns["simplex"].batch_bytes),
            sink=None if dfilt else _sink(c4)),
    }
    ins = {"extract": [], "sort": [c1], "group": [c2], "simplex": [c3]}
    outs = {"extract": [c1], "sort": [c2], "group": [c3],
            "simplex": [] if dfilt else [c4]}
    if not dfilt:
        calls["filter"] = lambda a: cmd_filter(a,
                                               source=ChannelBatchReader(c4))
        ins["filter"] = [c4]
        outs["filter"] = []

    lock = _threading.Lock()
    results = {}
    active = {}

    def runner(name):
        # this stage's helper threads: chain-<stage>-reader / -writer /
        # -worker-i (the stage thread runs in a context copy of its own)
        set_thread_prefix(f"chain-{name}")
        sargs = ns[name]
        t0 = time.monotonic()
        rc = None
        err = None
        aborted = False
        with lock:
            active[name] = True
        # one span for the stage thread's life: its waits (chain.put/get,
        # queue waits) are children, so wall_s - wait_s is the stage's own
        # work on this thread, with the thread's CPU seconds and faults
        stage_span = span(f"chain.{name}", rusage=True)
        stage_span.__enter__()
        try:
            # per-stage compat mapping (BGZF level contextvar etc.) runs in
            # this thread's context copy, so stages stay isolated exactly
            # like the staged driver's per-main() invocations
            rc = _apply_pipeline_compat(sargs)
            if rc == 0:
                sargs.func = calls[name]
                rc = _run_command(sargs)
        except ChainAborted:
            aborted = True  # cascade victim; the root cause is elsewhere
        except BaseException as e:  # noqa: BLE001 - relayed to the driver
            err = e
        finally:
            stage_span.__exit__(None, None, None)
            wall = time.monotonic() - t0
            with lock:
                active.pop(name, None)
                results[name] = {"rc": rc, "error": err, "aborted": aborted}
            METRICS.inc(f"pipeline.stage.{name}.wall_s", round(wall, 6))
            ok = rc == 0 and err is None and not aborted
            if ok:
                # the stage's writer already closed its channel; this close
                # is an idempotent backstop
                for c in outs[name]:
                    c.close()
                log.info("pipeline: %s done in %.2fs", name, wall)
            else:
                for c in outs[name]:
                    c.abort(f"pipeline stage {name} failed")
                for c in ins[name]:
                    c.cancel()

    METRICS.set("pipeline.chain.fused", 1)

    def _running_stages():
        # a started stage parked in its input-header wait (group/simplex/
        # filter until the sort merge opens the segment) is not "running"
        # yet — the heartbeat should show the stages actually doing work,
        # e.g. stage=extract+sort during the ingest-overlap phase
        with lock:
            started = [n for n, _ in stages if n in active]
        return {"stage": "+".join(
            n for n in started
            if all(c.has_header for c in ins[n])) or "-"}

    gauge_token = _hb.register_gauge(_running_stages)
    t00 = time.monotonic()
    threads = []
    # the job's open phase ends as the stage threads start, its close
    # begins where they are joined (this thread runs no pipeline itself)
    from .observe.process import job_here

    job = job_here()
    try:
        for name, _ in stages:
            t = spawn_thread(runner, args=(name,),
                             name=f"fgumi-chain-{name}")
            threads.append(t)
            t.start()
        if job is not None:
            job.begin(None)
        try:
            for t in threads:
                while t.is_alive():
                    t.join(timeout=0.2)
        except BaseException:
            # KeyboardInterrupt (or anything else) on the driver thread:
            # tear the chain down so every stage unwinds, then re-raise for
            # the top-level exit-code mapping
            for c in chans:
                c.abort("pipeline interrupted")
                c.cancel()
            for t in threads:
                t.join(timeout=10)
            raise
    finally:
        if job is not None:
            job.begin("close")
        _hb.unregister_gauge(gauge_token)
        for c in chans:
            c.fold_metrics()
    for name, _ in stages:
        r = results.get(name)
        if r is None:
            continue
        if r["error"] is not None:
            raise r["error"]
        if r["rc"] not in (0, None):
            log.error("pipeline: stage %s failed (rc=%d)", name, r["rc"])
            return r["rc"]
    aborted = [n for n, _ in stages if results.get(n, {}).get("aborted")]
    if aborted:
        log.error("pipeline: stage(s) %s aborted with no root cause "
                  "recorded", ",".join(aborted))
        return 1
    log.info("pipeline: total %.2fs (fused) -> %s", time.monotonic() - t00,
             args.output)
    return 0


def _pipeline_staged(args):
    """The classic staged driver: each stage re-enters main() and writes a
    level-0 intermediate BAM (tmpfs-backed when the host has headroom),
    deleted as soon as the next stage has consumed it."""
    import shutil
    import tempfile

    out_dir = os.path.dirname(os.path.abspath(args.output)) or "."
    keep = args.keep_intermediates
    # intermediates are transient by design — put them on tmpfs when the
    # host has one (file writes become memory copies; ~0.7s of the chain
    # on the bench workload was BufferedWriter.write to disk-backed tmp),
    # falling back next to the output. --keep-intermediates keeps the
    # user-visible directory on the output filesystem as before.
    if keep:
        tmp = keep
        os.makedirs(tmp, exist_ok=True)
    else:
        tmp_parent = out_dir
        shm = "/dev/shm"
        if os.path.isdir(shm) and os.access(shm, os.W_OK):
            try:
                # stored (level-0) intermediates expand gzip inputs ~4x and
                # up to two are alive at once; only use tmpfs when it has
                # clear headroom, else intermediates stay disk-backed
                from .utils.memory import _mem_available

                need = 8 * sum(os.path.getsize(p) for p in args.input)
                st = os.statvfs(shm)
                headroom = st.f_bavail * st.f_frsize
                # tmpfs "free" is the mount quota, not free RAM: tmpfs
                # pages consume physical memory, so also require real
                # MemAvailable headroom or risk inviting the OOM killer
                avail = _mem_available()
                if avail is not None:
                    headroom = min(headroom, avail)
                if headroom > 2 * need:
                    tmp_parent = shm
            except OSError:
                pass
        tmp = tempfile.mkdtemp(prefix="fgumi_pipeline_", dir=tmp_parent)

    def j(name):
        return os.path.join(tmp, name)

    # each stage re-enters main(), which resets the atomic-commit global
    # from its own flags — so an outer --no-atomic-output must travel
    pre = ["--no-atomic-output"] if args.no_atomic_output else []
    if args.audit_output:
        pre.append("--audit-output")
    stages = _pipeline_stage_argvs(args, j)
    consumed = {"sort": "unmapped.bam", "group": "sorted.bam",
                "simplex": "grouped.bam", "filter": "cons.bam"}
    from .observe import heartbeat as _hb
    from .observe.metrics import METRICS

    current = {"stage": "-"}
    gauge_token = _hb.register_gauge(lambda: dict(current))
    try:
        t00 = time.monotonic()
        for name, argv in stages:
            current["stage"] = name
            t0 = time.monotonic()
            rc = main(pre + argv)
            dt = time.monotonic() - t0
            METRICS.inc(f"pipeline.stage.{name}.wall_s", round(dt, 6))
            if rc:
                log.error("pipeline: stage %s failed (rc=%d)", name, rc)
                return rc
            log.info("pipeline: %s done in %.2fs", name, dt)
            prev = consumed.get(name)
            if prev and not keep:
                try:
                    os.unlink(j(prev))
                except OSError:
                    pass
        log.info("pipeline: total %.2fs -> %s", time.monotonic() - t00,
                 args.output)
    finally:
        _hb.unregister_gauge(gauge_token)
        if not keep:
            shutil.rmtree(tmp, ignore_errors=True)
    return 0


def _add_serve(sub):
    p = sub.add_parser(
        "serve",
        help="Run the persistent job-service daemon (warm-kernel serving)")
    p.add_argument("--socket", default=None, metavar="PATH",
                   help="Unix-domain socket path to listen on (docs/"
                        "serving.md; relative job paths resolve against "
                        "the daemon's working directory). At least one of "
                        "--socket/--tcp is required")
    p.add_argument("--tcp", default=None, metavar="HOST:PORT",
                   help="additionally listen on TCP (fleet operation): "
                        "per-connection read/write deadlines "
                        "(--io-timeout), a connection cap (--conn-cap), "
                        "and — for any non-loopback HOST — a REQUIRED "
                        "shared-secret handshake (--token-file or "
                        "FGUMI_TPU_SERVE_TOKEN; the wire protocol "
                        "executes submitted commands). Port 0 binds an "
                        "ephemeral port. A busy port exits 2 before the "
                        "device warm-up")
    p.add_argument("--token-file", default=None, metavar="PATH",
                   help="file holding the shared-secret handshake token "
                        "for TCP connections (surrounding whitespace "
                        "stripped; default: FGUMI_TPU_SERVE_TOKEN)")
    p.add_argument("--conn-cap", type=int, default=None, metavar="N",
                   help="max concurrent TCP connections; over-cap "
                        "connects are answered with one explicit error "
                        "frame and closed (default 64; 0 = unlimited)")
    p.add_argument("--io-timeout", type=float, default=None, metavar="S",
                   help="per-connection read/write deadline on TCP "
                        "connections (default 30; 0 = none)")
    p.add_argument("--workers", type=int, default=2,
                   help="concurrent job slots (bounded worker pool)")
    p.add_argument("--queue-limit", type=int, default=8,
                   help="queued jobs admitted beyond the running ones; "
                        "submissions past workers+queue-limit are rejected "
                        "with an explicit reason")
    p.add_argument("--max-per-client", type=int, default=0,
                   help="per-submitter admission quota: a `submit "
                        "--client ID` may hold at most this many active "
                        "(queued+running) jobs; over-quota submits are "
                        "rejected with an explicit reason (0 = unlimited; "
                        "anonymous submits are never limited)")
    p.add_argument("--report-dir", default=None, metavar="DIR",
                   help="write per-job run reports (<job>.report.json) and "
                        "on-request traces here (created if missing)")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="persistent XLA compile-cache directory for warm "
                        "serving (default: <checkout>/.jax_cache; ignored "
                        "when JAX_COMPILATION_CACHE_DIR is set, which then "
                        "names the directory)")
    p.add_argument("--max-frame-bytes", type=int, default=None,
                   help="protocol frame size cap (default 1 MiB); larger "
                        "frames are rejected and the connection closed")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip the startup jax import/device touch (first "
                        "job pays cold start instead)")
    p.add_argument("--journal", default=None, metavar="PATH",
                   help="append-only job journal (JSONL WAL): submits and "
                        "state transitions are fsync'd here, and on "
                        "restart incomplete jobs are requeued in order "
                        "(docs/serving.md crash recovery). Unset = "
                        "in-memory only, the pre-journal behavior")
    p.add_argument("--journal-dir", default=None, metavar="DIR",
                   help="FLEET journaling: journal at DIR/<fleet-id>."
                        "journal with an fcntl lease held for the "
                        "daemon's lifetime. Daemons sharing DIR (one real "
                        "filesystem) take over a dead peer's journal "
                        "exactly once and requeue its incomplete jobs "
                        "under their original ids (docs/serving.md "
                        "\"Fleet operation\"). Exclusive with --journal")
    p.add_argument("--fleet-id", default=None, metavar="NAME",
                   help="this daemon's identity in --journal-dir "
                        "([A-Za-z0-9._-], <=64 chars; job ids become "
                        "<fleet-id>-j-<n> so they are fleet-unique). "
                        "Default: derived from the socket basename or "
                        "the TCP port")
    p.add_argument("--lease-scan-period", type=float, default=2.0,
                   metavar="S",
                   help="how often the fleet lease scanner probes peer "
                        "journals for takeover (0 = never scan; the "
                        "daemon still recovers its own journal)")
    p.add_argument("--health-period", type=float, default=None,
                   metavar="S",
                   help="run a tiny device canary every S seconds feeding "
                        "the wedge circuit breaker (default: "
                        "FGUMI_TPU_HEALTH_PERIOD_S, else off)")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="serve Prometheus text-format /metrics and a "
                        "/healthz liveness endpoint on this loopback HTTP "
                        "port (0 = an ephemeral port, logged at startup; "
                        "unset = no listener). The scrape and the `stats` "
                        "protocol op read the same live snapshot "
                        "(docs/serving.md)")
    p.add_argument("--coalesce-window-ms", type=float, default=None,
                   metavar="MS",
                   help="cross-job dispatch coalescing window: while >= 2 "
                        "jobs are running, compatible device batches from "
                        "different jobs are held up to this long and "
                        "merged into one launch, split back per job at "
                        "resolve (byte-identical per job; docs/serving.md "
                        "\"Cross-job batching\"). 0 disables; default: "
                        "FGUMI_TPU_COALESCE_WINDOW_MS, else 2")
    p.set_defaults(func=cmd_serve)


def _default_fleet_id(args):
    """A stable default identity in --journal-dir: the socket basename
    (without extension) or the TCP port. Good enough for one-host fleets;
    multi-host fleets should pass --fleet-id explicitly. Returns None
    when no stable default exists (ephemeral --tcp port 0: every such
    daemon would collide on the same lease)."""
    import re as _re

    if args.socket:
        base = os.path.basename(args.socket)
        base = base[:-5] if base.endswith(".sock") else base
        base = _re.sub(r"[^A-Za-z0-9._-]", "-", base).strip("-.")
        if base:
            return base[:64]
    if args.tcp:
        port = args.tcp.rsplit(":", 1)[-1]
        if port != "0":
            return "tcp-" + port
    return None


def cmd_serve(args):
    import signal

    from .serve import transport as transport_mod
    from .serve.daemon import JobService, SocketBusy
    from .serve.journal import LeaseHeld

    if not args.socket and not args.tcp:
        log.error("serve needs --socket and/or --tcp")
        return 2
    if args.workers < 1:
        log.error("--workers must be >= 1")
        return 2
    if args.queue_limit < 0:
        log.error("--queue-limit must be >= 0")
        return 2
    if args.max_per_client < 0:
        log.error("--max-per-client must be >= 0")
        return 2
    if args.max_frame_bytes is not None and args.max_frame_bytes < 1024:
        # a sub-1KiB cap cannot carry a realistic submit frame, and 0 or a
        # negative value would defeat the size limit entirely
        log.error("--max-frame-bytes must be >= 1024")
        return 2
    if args.metrics_port is not None \
            and not 0 <= args.metrics_port <= 65535:
        log.error("--metrics-port must be in 0..65535")
        return 2
    if args.journal and args.journal_dir:
        log.error("--journal and --journal-dir are exclusive")
        return 2
    if args.conn_cap is not None and args.conn_cap < 0:
        log.error("--conn-cap must be >= 0 (0 = unlimited)")
        return 2
    if args.coalesce_window_ms is not None:
        if args.coalesce_window_ms < 0:
            log.error("--coalesce-window-ms must be >= 0 (0 = off)")
            return 2
        # the coalescer reads the env per dispatch, so the flag is just
        # the daemon-scoped spelling of FGUMI_TPU_COALESCE_WINDOW_MS
        os.environ["FGUMI_TPU_COALESCE_WINDOW_MS"] = \
            str(args.coalesce_window_ms)
    if args.report_dir:
        try:
            os.makedirs(args.report_dir, exist_ok=True)
        except OSError as e:
            log.error("cannot create --report-dir %s: %s", args.report_dir, e)
            return 2
    tcp = None
    if args.tcp:
        try:
            kind, tcp = transport_mod.parse_address("tcp:" + args.tcp)
        except ValueError as e:
            log.error("--tcp: %s", e)
            return 2
    try:
        token = transport_mod.load_token(args.token_file)
    except (OSError, ValueError) as e:
        log.error("--token-file: %s", e)
        return 2
    from .ops.breaker import monitor_period_s
    from .serve import protocol as _proto

    health = args.health_period if args.health_period is not None \
        else monitor_period_s()
    if health < 0:
        log.error("--health-period must be >= 0")
        return 2
    fleet_id = None
    if args.journal_dir:
        fleet_id = args.fleet_id or _default_fleet_id(args)
        if fleet_id is None:
            log.error("--journal-dir with an ephemeral --tcp port has no "
                      "stable default identity; pass --fleet-id")
            return 2
    try:
        service = JobService(
            args.socket, workers=args.workers, queue_limit=args.queue_limit,
            report_dir=args.report_dir,
            max_frame_bytes=args.max_frame_bytes or _proto.MAX_FRAME_BYTES,
            journal_path=args.journal, health_period_s=health,
            max_per_client=args.max_per_client,
            metrics_port=args.metrics_port, tcp=tcp, auth_token=token,
            conn_cap=(args.conn_cap if args.conn_cap is not None
                      else transport_mod.DEFAULT_CONN_CAP),
            io_timeout_s=(args.io_timeout if args.io_timeout is not None
                          else transport_mod.DEFAULT_IO_TIMEOUT_S),
            journal_dir=args.journal_dir, fleet_id=fleet_id,
            lease_scan_period_s=args.lease_scan_period)
    except ValueError as e:
        log.error("%s", e)
        return 2
    # claim the listeners BEFORE the device warm-up: an accidental
    # duplicate start must fail fast without touching the single-tenant
    # chip — a busy TCP port or fleet lease is the same exit-2 contract
    try:
        service.bind()
        service.acquire_lease()
    except (SocketBusy, LeaseHeld) as e:
        log.error("%s", e)
        service.close()
        return 2
    except ValueError as e:
        # a refused listener configuration (non-loopback TCP without a
        # handshake token)
        log.error("%s", e)
        service.close()
        return 2
    except OSError as e:
        if service._unix is not None and service._unix.sock is None:
            log.error("cannot bind %s: %s", args.socket, e)
        elif args.tcp and (service._tcp_listener is None
                           or service._tcp_listener.sock is None):
            log.error("cannot bind tcp %s: %s", args.tcp, e)
        else:
            log.error("cannot bind metrics port %s: %s",
                      args.metrics_port, e)
        service.close()
        return 2
    try:
        service.warm_up(compile_cache_dir=args.compile_cache,
                        touch_device=not args.no_warmup)
    except RuntimeError as e:
        # jax found no usable backend (a chip held by another process, a
        # missing runtime): a failed start, not a daemon on the host engine
        log.error("serve: device warm-up failed: %s", e)
        service.close()
        return 1
    service.start()

    def _on_signal(signum, frame):
        # SIGTERM drain contract: stop admitting, finish queued + running.
        # Event-set only — no locks or logging in signal context; the main
        # loop below performs (and logs) the actual drain
        service.request_shutdown()

    old = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            old[sig] = signal.signal(sig, _on_signal)
        except (ValueError, OSError):
            pass  # not the main thread (in-process test harness)
    try:
        service.wait_until_shutdown()
    finally:
        for sig, handler in old.items():
            signal.signal(sig, handler)
        service.close()
    return 0


def _add_submit(sub):
    p = sub.add_parser(
        "submit",
        help="Submit a command to a running serve daemon (warm execution)")
    p.add_argument("--socket", required=True, metavar="ADDR",
                   help="daemon address: a Unix socket path (serve "
                        "--socket), unix:PATH, or tcp:HOST:PORT (serve "
                        "--tcp / a balance front end)")
    p.add_argument("--token-file", default=None, metavar="PATH",
                   help="shared-secret handshake token for TCP daemons "
                        "(default: FGUMI_TPU_SERVE_TOKEN)")
    p.add_argument("--priority", default="normal",
                   choices=["high", "normal", "low"],
                   help="scheduling class (FIFO within a class)")
    p.add_argument("--tag", default=None,
                   help="free-form label kept on the job record")
    p.add_argument("--job-trace", action="store_true",
                   help="ask the daemon for a per-job Perfetto trace next "
                        "to the job's run report (needs serve --report-dir)")
    p.add_argument("--dedupe", default=None, metavar="KEY",
                   help="idempotency key: resubmitting the same key "
                        "returns the original job (even across a daemon "
                        "restart with serve --journal) instead of running "
                        "it twice")
    p.add_argument("--client", default=None, metavar="ID",
                   help="submitter identity for the daemon's per-client "
                        "admission quota (serve --max-per-client); "
                        "omitted = anonymous, never quota-limited")
    p.add_argument("--no-wait", action="store_true",
                   help="return immediately after admission (poll later "
                        "with `fgumi-tpu jobs`)")
    p.add_argument("--timeout", type=float, default=None,
                   help="max seconds to wait for completion (with waiting)")
    p.add_argument("job_argv", nargs=argparse.REMAINDER, metavar="COMMAND",
                   help="the fgumi-tpu command to run, e.g. "
                        "`submit --socket S simplex -i in.bam -o out.bam` "
                        "(everything after the submit options, verbatim)")
    p.set_defaults(func=cmd_submit)


def _submit_with_shed_retry(client, submit_kwargs: dict, wait: bool,
                            timeout: float = None, sleep=time.sleep):
    """Submit, honoring the governor's resource-pressure hint.

    A shed (``resource_pressure`` with ``retry_after_s``) is not a
    failure when the caller intends to wait: sleep EXACTLY the daemon's
    hint and resubmit instead of hot-looping or giving up, bounded by
    the overall ``timeout``. Raises the final ShedError when not waiting
    or out of time. ``sleep`` is injectable for tests."""
    from .serve.client import ShedError

    deadline = None if timeout is None else time.monotonic() + timeout
    while True:
        try:
            return client.submit(**submit_kwargs)
        except ShedError as e:
            if not wait:
                raise
            hint = max(float(e.retry_after_s), 0.05)
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise
                hint = min(hint, remaining)
            log.info("submit: daemon shedding under resource pressure; "
                     "retrying in %.1fs (%s)", hint, e)
            sleep(hint)


def _serve_client(args, label: str):
    """(client, rc) for the serve-client verbs: resolves the handshake
    token and the address; a config problem logs one line and returns
    (None, 2)."""
    from .serve import transport as transport_mod
    from .serve.client import ServeClient

    try:
        token = transport_mod.load_token(args.token_file)
        return ServeClient(args.socket, token=token), 0
    except (OSError, ValueError) as e:
        log.error("%s: %s", label, e)
        return None, 2


def cmd_submit(args):
    from .serve.client import ServeError

    job_argv = list(args.job_argv)
    if job_argv and job_argv[0] == "--":
        job_argv = job_argv[1:]
    if not job_argv:
        log.error("submit: no command given (usage: fgumi-tpu submit "
                  "--socket S <command> [args...])")
        return 2
    client, rc = _serve_client(args, "submit")
    if client is None:
        return rc
    # ONE wall-clock budget for the whole command: shed-retry sleeps and
    # the completion wait share it, so --timeout 60 means 60, not 120
    deadline = None if args.timeout is None \
        else time.monotonic() + args.timeout
    try:
        job = _submit_with_shed_retry(
            client,
            dict(argv=job_argv, priority=args.priority, tag=args.tag,
                 trace=args.job_trace, dedupe=args.dedupe,
                 client=args.client),
            wait=not args.no_wait, timeout=args.timeout)
    except ServeError as e:
        log.error("submit: %s", e)
        return 2
    log.info("submitted %s (%s): %s", job["id"], job["state"],
             " ".join(job["argv"]))
    if args.no_wait:
        print(job["id"])
        return 0
    try:
        job = client.wait(
            job["id"],
            timeout=None if deadline is None
            else max(deadline - time.monotonic(), 0.0))
    except ServeError as e:
        log.error("submit: %s", e)
        return 2
    rc = job["exit_status"]
    if job["state"] == "done":
        log.info("job %s done in %.2fs", job["id"],
                 job["finished_unix"] - job["started_unix"])
        return 0
    if job["state"] == "cancelled":
        log.error("job %s was cancelled", job["id"])
        return 130
    log.error("job %s failed: %s", job["id"], job["error"])
    return rc if isinstance(rc, int) and rc else 1


def _add_balance(sub):
    p = sub.add_parser(
        "balance",
        help="Run the fleet balancer: a health-routed front end over N "
             "serve daemons speaking the same wire protocol "
             "(docs/serving.md \"Fleet operation\")")
    p.add_argument("--listen", required=True, metavar="ADDR",
                   help="front-end address: unix:PATH or tcp:HOST:PORT "
                        "(non-loopback TCP requires the handshake token, "
                        "like serve --tcp; port 0 = ephemeral)")
    p.add_argument("--backend", action="append", required=True,
                   metavar="ADDR", dest="backends",
                   help="one serve daemon address (repeat per backend): "
                        "unix:PATH or tcp:HOST:PORT")
    p.add_argument("--token-file", default=None, metavar="PATH",
                   help="shared-secret handshake token used BOTH for the "
                        "front listener and toward TCP backends — a fleet "
                        "shares one secret (default: "
                        "FGUMI_TPU_SERVE_TOKEN)")
    p.add_argument("--poll-period", type=float, default=1.0, metavar="S",
                   help="health/depth poll period: each backend's `stats` "
                        "op feeds queue-depth routing and the ejection "
                        "breaker")
    p.add_argument("--eject-failures", type=int, default=2, metavar="N",
                   help="consecutive probe/request failures that eject a "
                        "backend (closed -> open)")
    p.add_argument("--cooldown", type=float, default=5.0, metavar="S",
                   help="ejection cooldown before the half-open re-probe "
                        "(doubles per consecutive re-trip, capped 8x)")
    p.add_argument("--probes", type=int, default=2, metavar="N",
                   help="consecutive half-open probe successes that "
                        "re-admit a backend")
    p.add_argument("--conn-cap", type=int, default=None, metavar="N",
                   help="max concurrent front-end TCP connections "
                        "(default 64)")
    p.add_argument("--io-timeout", type=float, default=None, metavar="S",
                   help="per-connection read/write deadline on front-end "
                        "TCP connections (default 30; 0 = none)")
    p.add_argument("--backend-timeout", type=float, default=30.0,
                   metavar="S",
                   help="per-request timeout toward a backend")
    p.add_argument("--max-frame-bytes", type=int, default=None,
                   help="protocol frame size cap (default 1 MiB)")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="serve the fleet Prometheus /metrics endpoint (+ a "
                        "/healthz that goes 503 when no backend is "
                        "routable) on this loopback HTTP port: fleet "
                        "rollups plus every backend's cached series "
                        "re-exported with a backend=\"ADDR\" label, from "
                        "the same health-poll snapshot the `stats` op "
                        "reports (0 = ephemeral; unset = no listener; "
                        "docs/serving.md \"Fleet metrics\")")
    g = p.add_argument_group("whale scatter/gather")
    g.add_argument("--scatter", type=int, default=0, metavar="N",
                   help="split every submitted pipeline/simplex/duplex "
                        "job into N dedupe-keyed shard sub-jobs fanned "
                        "out across the backends, then k-way merge the "
                        "shard outputs into ONE BAM byte-identical to a "
                        "single-backend run (N >= 2; 0 = off; requires a "
                        "filesystem shared with the backends; "
                        "docs/serving.md \"Scatter/gather\")")
    g.add_argument("--scatter-axis", default="umi",
                   choices=("umi", "coord"),
                   help="content-hash axis for the family split: the "
                        "UMI's MI value, or the template coordinate "
                        "(default umi; both are explicit hashes — "
                        "deterministic across hosts and Python hash "
                        "seeds)")
    g.add_argument("--scatter-wal", default=None, metavar="PATH",
                   help="fsync'd JSONL write-ahead log of whale/shard "
                        "state: a restarted balancer resumes in-flight "
                        "whales from it, resubmitting shards under their "
                        "idempotent dedupe keys (unset = whales do not "
                        "survive a balancer restart)")
    g.add_argument("--scatter-grace", type=float, default=20.0,
                   metavar="S",
                   help="how long a shard job may stay unknown "
                        "fleet-wide before the coordinator requeues it "
                        "under an attempt-suffixed dedupe key — keep "
                        "this LONGER than the daemons' lease-scan "
                        "period so a journal takeover wins the race "
                        "(default 20)")
    p.set_defaults(func=cmd_balance)


def cmd_balance(args):
    import signal

    from .serve import protocol as _proto
    from .serve import transport as transport_mod
    from .serve.balancer import Balancer
    from .serve.daemon import SocketBusy

    if args.poll_period <= 0:
        log.error("--poll-period must be > 0")
        return 2
    if args.eject_failures < 1 or args.probes < 1:
        log.error("--eject-failures and --probes must be >= 1")
        return 2
    if args.max_frame_bytes is not None and args.max_frame_bytes < 1024:
        log.error("--max-frame-bytes must be >= 1024")
        return 2
    if args.metrics_port is not None \
            and not 0 <= args.metrics_port <= 65535:
        log.error("--metrics-port must be in 0..65535")
        return 2
    if args.scatter and args.scatter < 2:
        log.error("--scatter needs at least 2 shards (0 disables it)")
        return 2
    if args.scatter_grace <= 0:
        log.error("--scatter-grace must be > 0")
        return 2
    try:
        token = transport_mod.load_token(args.token_file)
        for addr in [args.listen] + args.backends:
            transport_mod.parse_address(addr)
        balancer = Balancer(
            args.listen, args.backends, token=token, backend_token=token,
            max_frame_bytes=args.max_frame_bytes or _proto.MAX_FRAME_BYTES,
            poll_period_s=args.poll_period,
            eject_failures=args.eject_failures, cooldown_s=args.cooldown,
            probe_successes=args.probes,
            conn_cap=(args.conn_cap if args.conn_cap is not None
                      else transport_mod.DEFAULT_CONN_CAP),
            io_timeout_s=(args.io_timeout if args.io_timeout is not None
                          else transport_mod.DEFAULT_IO_TIMEOUT_S),
            backend_timeout_s=args.backend_timeout,
            metrics_port=args.metrics_port,
            scatter_shards=args.scatter, scatter_axis=args.scatter_axis,
            scatter_wal=args.scatter_wal,
            scatter_grace_s=args.scatter_grace)
    except (OSError, ValueError) as e:
        log.error("balance: %s", e)
        return 2
    try:
        balancer.bind()
    except SocketBusy as e:
        log.error("%s", e)
        return 2
    except OSError as e:
        log.error("cannot bind %s: %s", args.listen, e)
        return 2
    balancer.start()

    def _on_signal(signum, frame):
        # SIGTERM drain contract: event-set only; the main loop below
        # does the drain (and its logging) outside signal context
        balancer.request_shutdown()

    old = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            old[sig] = signal.signal(sig, _on_signal)
        except (ValueError, OSError):
            pass  # not the main thread (in-process test harness)
    try:
        balancer.wait_until_shutdown()
    finally:
        for sig, handler in old.items():
            signal.signal(sig, handler)
        balancer.close()
    return 0


def _add_stats(sub):
    p = sub.add_parser(
        "stats",
        help="Print a running serve daemon's live introspection snapshot "
             "(scheduler/quota/journal/breaker/governor/device/fleet "
             "state + latency histogram summaries) as JSON")
    p.add_argument("--socket", required=True, metavar="ADDR",
                   help="daemon address: a Unix socket path, unix:PATH, "
                        "or tcp:HOST:PORT (a balance front end answers "
                        "with per-backend health)")
    p.add_argument("--token-file", default=None, metavar="PATH",
                   help="shared-secret handshake token for TCP daemons "
                        "(default: FGUMI_TPU_SERVE_TOKEN)")
    p.add_argument("--section", default=None, metavar="KEY",
                   help="print only one top-level section of the snapshot "
                        "(e.g. latency, scheduler, breaker)")
    p.set_defaults(func=cmd_stats)


def cmd_stats(args):
    import json as _json

    from .serve.client import ServeError

    client, rc = _serve_client(args, "stats")
    if client is None:
        return rc
    try:
        stats = client.stats()
    except ServeError as e:
        # includes the old-daemon rejection ("unknown op 'stats' ...")
        # verbatim — the version-negotiation contract
        log.error("stats: %s", e)
        return 2
    if args.section is not None:
        if args.section not in stats:
            log.error("stats: no section %r (have: %s)", args.section,
                      ", ".join(sorted(stats)))
            return 2
        stats = {args.section: stats[args.section]}
    print(_json.dumps(stats, indent=1, sort_keys=True))
    return 0


def _add_jobs(sub):
    p = sub.add_parser(
        "jobs", help="Inspect or manage a serve daemon's job queue")
    p.add_argument("--socket", required=True, metavar="ADDR",
                   help="daemon address: a Unix socket path, unix:PATH, "
                        "or tcp:HOST:PORT")
    p.add_argument("--token-file", default=None, metavar="PATH",
                   help="shared-secret handshake token for TCP daemons "
                        "(default: FGUMI_TPU_SERVE_TOKEN)")
    g = p.add_mutually_exclusive_group()
    g.add_argument("--id", default=None, help="show one job as JSON")
    g.add_argument("--cancel", default=None, metavar="ID",
                   help="cancel a queued job")
    g.add_argument("--drain", action="store_true",
                   help="close admission (running/queued jobs finish; the "
                        "daemon keeps answering status)")
    g.add_argument("--shutdown", action="store_true",
                   help="drain, finish queued+running jobs, then exit")
    g.add_argument("--ping", action="store_true",
                   help="print daemon liveness/config as JSON")
    g.add_argument("--scatter", nargs="?", const="", default=None,
                   metavar="WHALE_ID",
                   help="print a `balance --scatter` front end's whale "
                        "scatter section as JSON (with WHALE_ID: that "
                        "whale's per-shard states); daemons and "
                        "non-scatter balancers answer their documented "
                        "refusal (docs/serving.md \"Whale "
                        "scatter/gather\")")
    p.set_defaults(func=cmd_jobs)


def cmd_jobs(args):
    import json as _json

    from .serve.client import ServeError

    client, rc = _serve_client(args, "jobs")
    if client is None:
        return rc
    try:
        if args.ping:
            print(_json.dumps(client.ping(), indent=1, sort_keys=True))
            return 0
        if args.scatter is not None:
            sc = client.scatter(args.scatter or None)
            print(_json.dumps(sc, indent=1, sort_keys=True))
            return 0
        if args.id:
            print(_json.dumps(client.job(args.id), indent=1, sort_keys=True))
            return 0
        if args.cancel:
            job = client.cancel(args.cancel)
            log.info("job %s cancelled", job["id"])
            return 0
        if args.drain:
            depth = client.drain()
            if "running" in depth:
                log.info("draining: %d running, %d queued",
                         depth["running"], depth["queued"])
            else:  # a balance front answers with its own (depthless) ack
                log.info("draining: balancer admission closed")
            return 0
        if args.shutdown:
            depth = client.shutdown()
            if "running" in depth:
                log.info("shutdown requested: %d running, %d queued to "
                         "finish", depth["running"], depth["queued"])
            else:
                log.info("shutdown requested: balancer draining and "
                         "exiting")
            return 0
        status = client.status()
        jobs = status["jobs"]
        if not jobs:
            print("no jobs")
            return 0
        print(f"{'id':<8} {'state':<10} {'prio':<7} {'rc':<4} command")
        for j in jobs:
            rc = "" if j["exit_status"] is None else str(j["exit_status"])
            print(f"{j['id']:<8} {j['state']:<10} {j['priority']:<7} "
                  f"{rc:<4} {' '.join(j['argv'])}")
        return 0
    except ServeError as e:
        log.error("jobs: %s", e)
        return 2


def _add_trace_merge(sub):
    p = sub.add_parser(
        "trace-merge",
        help="Stitch per-process --trace files from one fleet-routed job "
             "(client, balancer, backend) into a single Perfetto "
             "timeline, clock-aligned on each file's wall-clock anchor "
             "(docs/observability.md \"Fleet tracing\")")
    p.add_argument("traces", nargs="+", metavar="TRACE.json",
                   help="Chrome trace-event files to merge (each process's "
                        "--trace output)")
    p.add_argument("-o", "--output", required=True, metavar="PATH",
                   help="merged trace file to write")
    p.add_argument("--trace-id", default=None, metavar="HEX32",
                   help="keep only inputs stamped with this fleet trace "
                        "id; others are skipped (recorded under "
                        "otherData.skipped)")
    p.add_argument("--shift", action="append", default=None,
                   metavar="FILE=SECONDS", dest="shifts",
                   help="add SECONDS to FILE's timeline on top of the "
                        "automatic anchor/handshake-offset alignment "
                        "(FILE matches the path or its basename; repeat "
                        "per file)")
    p.add_argument("--force", action="store_true",
                   help="merge even when the inputs carry different trace "
                        "ids (default: that is an error)")
    p.set_defaults(func=cmd_trace_merge)


def cmd_trace_merge(args):
    from .observe.trace_merge import (MergeError, merge_traces,
                                      parse_shift_specs, write_merged)

    try:
        shifts = parse_shift_specs(args.shifts)
        merged = merge_traces(args.traces, trace_id=args.trace_id,
                              shifts=shifts, force=args.force)
        write_merged(merged, args.output)
    except MergeError as e:
        log.error("trace-merge: %s", e)
        return 2
    except OSError as e:
        log.error("trace-merge: cannot write %s: %s", args.output, e)
        return 2
    skipped = (merged.get("otherData") or {}).get("skipped") or []
    for s in skipped:
        log.info("trace-merge: skipped %s (trace id %s)", s["path"],
                 s.get("trace_id"))
    merged_from = merged["otherData"]["merged_from"]
    log.info("trace-merge: merged %d file(s), %d event(s) -> %s",
             len(merged_from), len(merged["traceEvents"]), args.output)
    return 0


def _add_tune(sub):
    p = sub.add_parser(
        "tune",
        help="Measure this host's device/host crossovers on a simulated "
             "workload matrix and write a deployment profile (tuned "
             "knobs + measured router priors, loaded via "
             "--profile/FGUMI_TPU_PROFILE) plus a crossover atlas "
             "(docs/performance-tuning.md \"Deployment profiles\")")
    p.add_argument("-o", "--output", default="deploy_profile.json",
                   metavar="PATH",
                   help="deployment profile to write")
    p.add_argument("--atlas", default="TUNE_ATLAS.json", metavar="PATH",
                   help="crossover atlas to write ('' disables)")
    p.add_argument("--quick", action="store_true",
                   help="CI-sized sweep: the three fixed-depth crossover "
                        "cells only, small pileups (seconds, not minutes)")
    p.add_argument("--replay", action="append", default=None,
                   metavar="JSON", dest="replay",
                   help="derive the profile from recorded evidence "
                        "instead of sweeping: run-report JSONs "
                        "(device.routing EWMAs) and/or microbench JSONs "
                        "(tune_cells from the --backend matrix); repeat "
                        "per file")
    p.set_defaults(func=cmd_tune)


def cmd_tune(args):
    from .tune.autotune import run_autotune
    from .tune.profile import ProfileError

    try:
        return run_autotune(args.output, args.atlas or None,
                            quick=args.quick, replay_paths=args.replay)
    except ProfileError as e:
        log.error("%s", e)
        return 2


def build_parser():
    parser = argparse.ArgumentParser(
        prog="fgumi-tpu",
        description="TPU-native toolkit for UMI-tagged sequencing data",
    )
    parser.add_argument("-v", "--verbose", action="store_true",
                        help="alias for --log-level debug (superseded by an "
                             "explicit --log-level)")
    parser.add_argument(
        "--log-level", choices=["debug", "info", "warning", "error"],
        default=None,
        help="log verbosity (also FGUMI_TPU_LOG); every line carries "
             "elapsed time and the emitting thread's name")
    parser.add_argument(
        "--no-atomic-output", action="store_true",
        help="write outputs directly to their final names instead of the "
             "crash-safe temp-file + atomic-rename commit (escape hatch "
             "for FIFO outputs; also FGUMI_TPU_NO_ATOMIC=1)")
    parser.add_argument(
        "--audit-output", action="store_true",
        help="verify every written BAM end to end (per-member BGZF "
             "CRC32/ISIZE, BAM structure, record count and sort-key-order "
             "digest against the writer's own tallies) BEFORE the atomic "
             "rename publishes it; a mismatch aborts the commit with exit "
             "5 so host-side corruption cannot ship a bad file "
             "(also FGUMI_TPU_AUDIT_OUTPUT=1; docs/resilience.md)")
    parser.add_argument(
        "--trace", default=None, metavar="PATH",
        help="record pipeline/IO/device spans and write a Chrome "
             "trace-event JSON loadable in Perfetto (also FGUMI_TPU_TRACE)")
    parser.add_argument(
        "--xla-profile", default=None, metavar="DIR",
        help="capture a one-shot jax.profiler device trace of the first "
             "device dispatch into DIR (TensorBoard/xprof format; "
             "FGUMI_TPU_XLA_PROFILE_NTH=N profiles the Nth dispatch "
             "instead — N=2 skips the XLA compile); the run report "
             "records the directory (also FGUMI_TPU_XLA_PROFILE)")
    parser.add_argument(
        "--run-report", default=None, metavar="PATH",
        help="write a schema-versioned JSON run report (wall time, "
             "per-stage busy/blocked, queue occupancy, device + I/O "
             "counters, exit status) at command end "
             "(also FGUMI_TPU_RUN_REPORT)")
    parser.add_argument(
        "--heartbeat", type=float, default=None, metavar="SECONDS",
        help="log a one-line progress heartbeat (stage counters, queue "
             "depths, device activity, p99 dispatch wall, records/s + ETA, "
             "RSS) every N seconds "
             "(also FGUMI_TPU_HEARTBEAT_S; 0 = off, the default)")
    parser.add_argument(
        "--flight-dump-dir", default=None, metavar="DIR",
        help="write flight-recorder black boxes (ring of recent events + "
             "all-thread stacks + metrics/device/breaker/governor "
             "snapshots) here on unhandled exceptions, resource "
             "exhaustion, dispatch-deadline overruns, breaker trips, and "
             "SIGTERM (also FGUMI_TPU_FLIGHT; unset = record the ring but "
             "never write a file)")
    parser.add_argument(
        "--profile", default=None, metavar="PATH",
        help="load a deployment profile (fgumi-tpu tune output): tuned "
             "knob values fill any FGUMI_TPU_* vars not explicitly set "
             "(explicit env/flags always win) and measured router "
             "priors seed the adaptive offload machinery so the first "
             "batch routes on the measured side of each crossover "
             "(also FGUMI_TPU_PROFILE; docs/performance-tuning.md)")
    parser.add_argument(
        "--shape-buckets", type=_shape_buckets_arg, default=None,
        metavar="GROWTH[:CAP]",
        help="device padded-shape bucket ladder: geometric growth factor "
             "in [1.01, 2.0] between adjacent buckets (default 1.0625) and "
             "optional ladder cap (default 2^24); bounds the XLA "
             "executable vocabulary and the padding waste "
             "(also FGUMI_TPU_SHAPE_BUCKETS; docs/device-datapath.md)")
    parser.add_argument(
        "--mesh", type=_mesh_arg, default=None, metavar="dpNxspM",
        help="device mesh for sharded consensus dispatch: dpNxspM forces "
             "an exact (data-parallel x sequence-parallel) shape validated "
             "against the visible device count, 'auto' uses every device, "
             "'off' disables sharding; overrides --devices/FGUMI_TPU_SP "
             "(also FGUMI_TPU_MESH; docs/multi-chip.md)")
    sub = parser.add_subparsers(dest="command", required=True)
    _add_extract(sub)
    _add_correct(sub)
    _add_zipper(sub)
    _add_simplex(sub)
    _add_duplex(sub)
    _add_codec(sub)
    _add_duplex_metrics(sub)
    _add_simplex_metrics(sub)
    _add_review(sub)
    _add_compare(sub)
    _add_filter(sub)
    _add_clip(sub)
    _add_group(sub)
    _add_dedup(sub)
    _add_sort(sub)
    _add_merge(sub)
    _add_fastq(sub)
    _add_downsample(sub)
    _add_simulate(sub)
    _add_pipeline(sub)
    _add_serve(sub)
    _add_submit(sub)
    _add_jobs(sub)
    _add_stats(sub)
    _add_balance(sub)
    _add_trace_merge(sub)
    _add_tune(sub)
    return parser


# nesting depth of in-process main() calls: the `pipeline` command re-enters
# main() per stage, and the telemetry lifecycle (trace export, run report,
# per-command scope) belongs to the OUTERMOST invocation only. A contextvar,
# not a module global: the serve daemon runs several top-level commands
# concurrently on worker threads, and each must see its own depth
import contextvars

_main_depth = contextvars.ContextVar("fgumi_tpu_main_depth", default=0)


def _run_command(args):
    """Dispatch to the subcommand with the top-level exception contract."""
    import errno as _errno

    from .io.errors import InputFormatError, OutputIntegrityError
    from .parallel import MeshConfigError
    from .utils.faults import InjectedFault
    from .utils.governor import GOVERNOR, ResourceExhausted

    try:
        pg = getattr(args, "pg_argv", None)
        if pg:
            # scatter sub-job provenance: @PG CL (and every other argv-
            # derived header field) records the WHALE job's command line,
            # so shard outputs are byte-compatible with the unsharded run.
            # Innermost wins over the daemon's per-job command_argv wrap.
            import shlex as _shlex

            from .observe.scope import command_argv

            with command_argv(_shlex.split(pg)):
                return args.func(args)
        return args.func(args)
    except MeshConfigError as e:
        # an unsatisfiable --mesh/FGUMI_TPU_MESH shape: one loud line, not
        # a traceback — a silently smaller mesh would misreport itself
        log.error("%s", e)
        return 2
    except (InputFormatError, EOFError) as e:
        # a diagnosed input problem (truncated/corrupt stream, torn record):
        # one line with path + offset, nonzero exit — not a traceback
        log.error("%s", e)
        return 2
    except InjectedFault as e:
        # chaos testing: an injected fault that propagated to the top is a
        # *clean* failure (distinct rc so the harness can tell it apart)
        log.error("%s", e)
        return 3
    except OutputIntegrityError as e:
        # the --audit-output pre-commit pass refuted the written file: the
        # atomic rename was aborted (no partial/corrupt file published)
        # and the black box carries the evidence — a distinct exit code so
        # harnesses can tell "the output would have been wrong" from every
        # other failure class (docs/resilience.md)
        from .observe.flight import FLIGHT

        FLIGHT.dump("output-integrity", exc=e)
        log.error("%s", e)
        return 5
    except ResourceExhausted as e:
        # resource hard limit (disk full, RSS hard watermark): atomic temps
        # were swept by the ordinary error unwinding; the run report gets a
        # `resource` section from the governor's event log, and the flight
        # recorder freezes a black box (ring + thread stacks + governor
        # snapshot) naming what was starved
        from .observe.flight import FLIGHT

        FLIGHT.dump("resource-exhausted", exc=e)
        log.error("%s", e)
        return 4
    except BrokenPipeError:
        # before the OSError backstop: BrokenPipeError IS an OSError, and a
        # bare raise there would skip this clause entirely. Detach stdout so
        # the interpreter's exit-time flush of the still-buffered stream
        # doesn't print "Exception ignored" noise
        try:
            os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        except OSError:
            pass
        return 1
    except OSError as e:
        if e.errno == _errno.ENOSPC:
            # backstop for any disk write not explicitly hardened: same
            # exit-code contract as the converted paths
            GOVERNOR.record_event("enospc", where="unhandled")
            log.error("disk full: %s", e)
            return 4
        raise
    except KeyboardInterrupt:
        log.error("interrupted")
        return 130


def _shape_buckets_arg(value: str) -> str:
    """argparse validator for --shape-buckets: loud parse errors at the
    command line instead of at first device dispatch."""
    import argparse as _ap

    from .ops.datapath import parse_shape_buckets

    try:
        parse_shape_buckets(value)
    except ValueError as e:
        raise _ap.ArgumentTypeError(str(e)) from None
    return value


def _mesh_arg(value: str) -> str:
    """argparse validator for --mesh: loud format errors at the command
    line (the shape-vs-device-count check runs at mesh build, where the
    live device list exists). Pure-regex parse — no jax import here."""
    import argparse as _ap
    import re as _re

    v = value.strip().lower()
    if v in ("", "off", "none", "0", "1", "auto") \
            or _re.match(r"^dp\d+(xsp\d+)?$", v):
        return value
    raise _ap.ArgumentTypeError(
        f"--mesh {value!r}: expected 'auto', 'off', or 'dpNxspM' "
        f"(e.g. dp4xsp2)")


def _apply_shape_buckets(args):
    """Reconfigure the process-global shape-bucket ladder for this
    invocation; returns a zero-arg restore callable (or None).

    The environment is deliberately left untouched and the ladder reverts
    at command exit: in the serve daemon one job's flag must not leak into
    every later job (the ladder is still a process-wide property while
    jobs overlap — daemon operators set FGUMI_TPU_SHAPE_BUCKETS on the
    daemon itself instead). Nested ``pipeline`` stages run in-process at
    depth > 0 and inherit the configured registry."""
    spec = getattr(args, "shape_buckets", None)
    if not spec:
        return None
    from .ops.datapath import SHAPE_REGISTRY

    gen = SHAPE_REGISTRY.reconfigure(spec)

    def restore():
        # back to env/defaults — unless a concurrent invocation (daemon
        # job) reconfigured since, in which case its ladder wins
        SHAPE_REGISTRY.reconfigure(only_if_gen=gen)

    return restore


def _telemetry_config(args):
    """(trace_path, report_path, heartbeat_s) from flags + environment."""
    trace_path = args.trace or os.environ.get("FGUMI_TPU_TRACE") or None
    report_path = (args.run_report
                   or os.environ.get("FGUMI_TPU_RUN_REPORT") or None)
    hb_s = args.heartbeat
    if hb_s is None:
        try:
            hb_s = float(os.environ.get("FGUMI_TPU_HEARTBEAT_S", "0") or 0)
        except ValueError:
            log.warning("FGUMI_TPU_HEARTBEAT_S=%s: not a number; heartbeat "
                        "off", os.environ["FGUMI_TPU_HEARTBEAT_S"])
            hb_s = 0.0
    return trace_path, report_path, hb_s


def main(argv=None):
    from .observe import process as _process

    _process.note_main()
    # every top-level invocation leaves an entry of its phases in the
    # process record, armed or not; a nested stage of a chained command
    # (depth > 0) is part of the outer one's
    with _process.job_record(nested=_main_depth.get() > 0):
        return _main(argv)


def _main(argv):
    parser = build_parser()
    args = parser.parse_args(argv)
    from .observe.logs import setup_logging

    # nested stages of a chained command (depth > 0) inherit the outer
    # invocation's level unless they carry an explicit flag: re-running
    # setup at the default would reset an operator's --log-level debug
    # back to info after the first `pipeline` stage
    depth = _main_depth.get()
    if depth == 0 or args.log_level or args.verbose:
        setup_logging(args.log_level, args.verbose)
    from .io.bam import set_audit_output
    from .utils.atomic import set_atomic_enabled

    set_atomic_enabled(not args.no_atomic_output)
    # set BOTH ways: the contextvar must not leak a previous in-process
    # invocation's flag into this one (nested pipeline stages re-enter
    # main() with the flag forwarded explicitly, like --no-atomic-output)
    set_audit_output(bool(args.audit_output))
    rc = _apply_pipeline_compat(args)
    if rc:
        return rc
    if depth > 0:
        # nested stage of a chained command: the outer invocation owns the
        # telemetry lifecycle; this stage just accumulates into it
        return _run_command(args)

    # per-command isolation: every top-level invocation gets its own
    # telemetry scope (metrics + DeviceStats + tracer), so back-to-back or
    # *concurrent* in-process commands — tests, the chained `pipeline`
    # driver, serve-daemon jobs on worker threads — never cross-contaminate
    # counters. Nested stages (depth > 0 above) inherit this scope through
    # the contextvar and accumulate into it, exactly like the old global
    # registries did under the outermost reset.
    from .observe.scope import (adopt_job_context, publish_to_global,
                                scoped_telemetry)

    # deployment profile (--profile / FGUMI_TPU_PROFILE): applied BEFORE
    # the telemetry scope so the env knobs it fills are in place for every
    # downstream env read, and process-once (a daemon job re-entering
    # main() in a fresh context must not re-apply or re-warn). A bad
    # profile is the same exit-2 contract as every other knob parse error.
    from .tune import profile as _profile

    try:
        _profile.maybe_apply_from_env(getattr(args, "profile", None))
    except _profile.ProfileError as e:
        log.error("%s", e)
        return 2

    restore_buckets = None
    try:
        restore_buckets = _apply_shape_buckets(args)
        with scoped_telemetry(args.command) as scope:
            # a serve-daemon job re-enters main() under a job_context: its
            # job id, propagated trace ids, and upstream hop timestamps
            # land on this scope (standalone runs: a no-op)
            adopt_job_context(scope)
            try:
                return _main_scoped(args, argv)
            finally:
                # legacy surface: leave the finished command's counters
                # visible on the process-global METRICS/DEVICE_STATS,
                # exactly like the old reset-at-entry globals did (bench/
                # probe harnesses read them right after cli_main returns)
                publish_to_global(scope)
    finally:
        # outside scoped_telemetry: the per-invocation ladder must revert
        # even when entering the scope itself raises, or a daemon job's
        # --shape-buckets would leak into every later job
        if restore_buckets is not None:
            restore_buckets()


def _main_scoped(args, argv):
    """The depth-0 command body: telemetry lifecycle around the dispatch
    (runs inside this invocation's telemetry scope)."""
    trace_path, report_path, hb_s = _telemetry_config(args)
    # arm the process-wide resource governor (dynamic budget rebalancing +
    # memory/disk pressure sentinels; FGUMI_TPU_GOVERNOR=0 keeps every
    # budget static). Idempotent — the thread is shared across commands.
    from .utils.governor import GOVERNOR

    GOVERNOR.maybe_start()
    # re-stamp the process's profile-application outcome into THIS
    # invocation's scoped registry (application itself is process-once)
    from .tune import profile as _profile

    _profile.stamp_metrics()
    # flight recorder destination: the ring always records; a configured
    # dump dir additionally turns failures into black-box files. The flag
    # sets the process-wide destination (like the env var it mirrors) —
    # daemon operators set it on the daemon, not per job.
    from .observe.flight import FLIGHT, install_signal_dump

    if getattr(args, "flight_dump_dir", None):
        FLIGHT.configure(args.flight_dump_dir)
    FLIGHT.note("command.start", command=args.command)
    install_signal_dump()
    # one-shot XLA device profile (--xla-profile): armed here, triggered
    # by the kernel's Nth dispatch, recorded in the run report
    xla_dir = (getattr(args, "xla_profile", None)
               or os.environ.get("FGUMI_TPU_XLA_PROFILE") or None)
    if xla_dir:
        from .observe import xprof

        try:
            nth = int(os.environ.get("FGUMI_TPU_XLA_PROFILE_NTH", "1") or 1)
        except ValueError:
            log.warning("FGUMI_TPU_XLA_PROFILE_NTH=%s: not a number; "
                        "profiling the first dispatch",
                        os.environ["FGUMI_TPU_XLA_PROFILE_NTH"])
            nth = 1
        xprof.configure(xla_dir, nth)
    tracer = hb = None
    armed = bool(trace_path or report_path)
    if armed:
        # spans are live under either flag: the aggregate (the report's
        # `spans` section) and the mirror onto the profiler's clock; the
        # Chrome tracer below only under --trace
        from .observe.trace import arm_spans

        agg = arm_spans()
        if report_path:
            # the allocator at the job's start; the report reads its end
            from .observe import alloc

            agg.alloc_start = alloc.read()
    if trace_path:
        from .observe.scope import current_scope
        from .observe.trace import start_trace

        tracer = start_trace()
        scope = current_scope()
        if scope is not None and (scope.trace_id or scope.job_id):
            # fleet-routed job: the per-job trace carries the propagated
            # context + a track-group label, so trace-merge can stitch it
            # under the client's trace-id next to the other processes
            tracer.set_context(
                trace_id=scope.trace_id,
                parent_span_id=scope.parent_span_id,
                process_label=(f"backend {scope.job_id}" if scope.job_id
                               else None))
    # the job's open phase: from here to the pipeline's first item
    from .observe.process import current_job

    job = current_job()
    if job is not None:
        job.begin("open")
    if hb_s > 0:
        from .observe.heartbeat import Heartbeat

        hb = Heartbeat(hb_s)
    t0 = time.monotonic()
    t0_unix = time.time()
    rc = 1  # report value when the command dies on an unmapped exception
    token = _main_depth.set(_main_depth.get() + 1)
    try:
        rc = _run_command(args)
        return rc
    except Exception as e:
        # anything _run_command's exit-code contract did not map is an
        # unhandled crash: freeze a black box before unwinding (the run
        # report below still records exit_status 1 + the dump path)
        FLIGHT.dump("unhandled-exception", exc=e)
        raise
    finally:
        _main_depth.reset(token)
        if job is not None:
            job.begin("report")  # what follows is the report's making
        if hb is not None:
            hb.stop()
        if tracer is not None:
            from .observe.trace import write_trace

            try:
                write_trace(trace_path, tracer)
                log.info("trace: %d spans -> %s (open in "
                         "https://ui.perfetto.dev)",
                         len(tracer.snapshot()), trace_path)
            except OSError as e:
                log.error("failed to write trace %s: %s", trace_path, e)
        # let in-flight shadow audits (ops/sentinel.py) reach their
        # verdicts before the command exits: a divergence found by a
        # background audit must still trip the breaker, write its black
        # box, and land in this run's report. Cheap when nothing is
        # pending; lazy so audit-free commands never import the module.
        _sentinel = sys.modules.get("fgumi_tpu.ops.sentinel")
        if _sentinel is not None and not _sentinel.SENTINEL.drain():
            log.warning("audit sentinel: background audits still pending "
                        "at command exit; report may undercount")
        if report_path:
            from .observe.report import emit, fold_device_stats

            fold_device_stats()
            GOVERNOR.fold_metrics()
            report = emit(report_path, args.command,
                          list(argv) if argv is not None else sys.argv[1:],
                          t0_unix, time.monotonic() - t0, rc, trace_path)
            if report is not None:
                log.info("run report -> %s", report_path)
        if armed:
            from .observe.trace import stop_trace

            stop_trace()  # after the report, which reads the aggregate


if __name__ == "__main__":
    sys.exit(main())
