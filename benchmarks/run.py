"""The benchmark's one command:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips: it brings up the served stack
(in-process Cluster with GTM + WAL + checkpoints under a run directory,
CnServer, CnClients over TCP), makes the data from --seed, loads, warms every
statement shape the window will use, measures for --seconds, checks every
reply against the plain reference, prints the contract's one JSON line last
and exits.  No chip -> it prints no result and exits 3; it never falls back
to the CPU.  `--rehearse-sf F` (with JAX_PLATFORMS=cpu) walks the same path
at scale factor F on the CPU as a rehearsal: its line says `correct: false`
and it exits 1, whatever it found.

A cell is one entry of `workloads` in BENCHMARK.json; everything about it is
found by name in data files (lib/files.py) and this file has no per-cell
code.
"""

import time

T0 = time.perf_counter()        # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from benchmarks.lib import files, profile, stats  # noqa: E402

XLA = {"requests": 0, "cache_hits": 0, "compile_s": 0.0}


def say(**fields):
    """A line of the run's own account, before the result line."""
    print(json.dumps(fields, default=str), flush=True)


def listen_to_jax():
    from jax import monitoring

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            XLA["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            XLA["cache_hits"] += 1

    def on_duration(event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            XLA["compile_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def counters():
    from opentenbase_tpu.exec import plancache
    from opentenbase_tpu.storage.bufferpool import POOL
    return {"programs": sum(r[3] for r in plancache.stats()),
            "xla_requests": XLA["requests"],
            "xla_cache_hits": XLA["cache_hits"],
            # requests the persistent cache did not serve: true compiles
            "xla_compiles": XLA["requests"] - XLA["cache_hits"],
            "xla_compile_s": XLA["compile_s"],
            "uploaded_bytes": POOL.totals()["uploaded_bytes"]}


def device_of():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peak_bytes():
    import jax
    peak = 0
    for d in jax.devices():
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


class Context:
    """What a reader may read: the window's requests, counters and trace."""

    def __init__(self, mix, data, requests, failed_steps, t_start,
                 t_end, counters_before, counters_after, peaks):
        self.mix, self.data, self.requests = mix, data, requests
        self.failed_steps = failed_steps
        self.window_s = t_end - t_start
        self.counters_before = counters_before
        self.counters_after = counters_after
        self.peaks = peaks
        self.trace = None
        self.trace_busy_s = self.trace_window_s = 0.0

    def steps(self):
        for r in self.requests:
            yield from r.steps

    def latencies_ms(self, classes=None):
        return [(t1 - t0) * 1e3 for cls, t0, t1, _r, err, _s in self.steps()
                if err is None and (not classes or cls in classes)]

    def latencies_ms_by_class(self, classes=None):
        by = {}
        for cls, t0, t1, _r, err, _s in self.steps():
            if err is None and (not classes or cls in classes):
                by.setdefault(cls, []).append((t1 - t0) * 1e3)
        return by

    def step_stats(self):
        return [(cls, st) for cls, _t0, _t1, _r, _e, st in self.steps()]

    def correct_statements(self, classes=None):
        n = sum(1 for cls, *_ in self.steps()
                if not classes or cls in classes)
        return n - self.failed_steps


def read_metric(ctx, spec):
    return files.reader(spec["reader"]).read(ctx, **spec.get("args", {}))


def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-sf", type=float, default=None,
                    help="CPU rehearsal at this scale factor: the whole "
                         "path, never a result that counts")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this "
                         "directory")
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json than the checkout's (a "
                         "cell that is data only, not yet a workload)")
    args = ap.parse_args()

    if args.benchmark_json:
        files.BENCHMARK_JSON = os.path.abspath(args.benchmark_json)
    bench = files.benchmark_json()
    cell = files.workload(args.workload)
    cfg = files.config(cell["config"])
    chips = int(cell["chips"])
    datanodes = int(cfg["datanodes"])
    rehearsal = args.rehearse_sf is not None

    # nothing goes to stdout unless there is a system and a device to
    # measure: alone in a directory, with jax's CPU standing in for a chip
    # that was wanted, or with fewer chips than the cell asks, the run says
    # why on stderr and exits 3
    try:
        import opentenbase_tpu  # noqa: F401  (x64 on before first use)
        dev = device_of()
    except (ImportError, RuntimeError) as e:
        print(f"benchmark: cannot start: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(3)
    on_chip = dev["platform"] == "tpu"
    if not on_chip and not (rehearsal
                            and os.environ.get("JAX_PLATFORMS") == "cpu"):
        print("benchmark: jax found no accelerator", file=sys.stderr)
        sys.exit(3)
    if dev["count"] < chips:
        print(f"benchmark: {args.workload} needs {chips} chips, jax reports "
              f"{dev['count']}", file=sys.stderr)
        sys.exit(3)

    import jax
    from opentenbase_tpu.exec import plancache
    from benchmarks.lib import datagen, peaks as peaks_mod, stack as stack_mod
    from benchmarks.lib.traffic import Mix

    peaks = peaks_mod.peaks_for(dev["kind"]) if on_chip else {}
    cache_dir = plancache.enable_persistent_cache()
    listen_to_jax()
    sf = args.rehearse_sf if rehearsal else float(cfg["scale_factor"])
    run_dir = os.path.join(BENCH_DIR, "run_out", args.workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    say(run=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=dev, scale_factor=sf, rehearsal=rehearsal,
        compile_cache_dir=cache_dir)

    stack = None
    failures = []
    try:
        # ---- set-up: data, stack, load, references, warm-up ------------
        t = time.perf_counter()
        data = datagen.generate(sf=sf, seed=args.seed)
        datagen_s = time.perf_counter() - t
        if datanodes > 1:
            from benchmarks.lib import mesh_check
            mesh_check.arm()
        stack = stack_mod.Stack(datanodes,
                                os.path.join(run_dir, "cluster"))
        conn0 = stack.connect()
        t = time.perf_counter()
        load_by_table = stack_mod.load_tpch(
            stack, conn0[0], data, tuple(cfg.get("copy_tables", ())), run_dir)
        load_s = time.perf_counter() - t

        mix = Mix(cell["traffic"], args.seed, data)
        t = time.perf_counter()
        mix.build_pools()
        reference_s = time.perf_counter() - t    # not part of setup_s

        for st in mix.statements:
            for sql in st.setup_statements():
                conn0[0].execute(sql)
        conns = [conn0] + [stack.connect() for _ in range(mix.clients - 1)]
        limits = files.load_json("lib", "limits.json")
        t = time.perf_counter()
        c_warm0 = counters()
        avg_gap = ulp_gap = 0.0
        for i, (client, session) in enumerate(conns):
            for req in mix.warm_requests(i):
                mix.run_request(req, client, session)
                bad, a, u = mix.check(req, limits)
                avg_gap, ulp_gap = max(avg_gap, a), max(ulp_gap, u)
                failures += [f"warm-up: {b}" for b in bad]
                for step, done in zip(req.stmt.steps, req.steps):
                    tier = (done[5] or {}).get("tier")
                    if step["check"] == "rows" \
                            and tier not in mix.served_tiers:
                        failures.append(f"warm-up: {step['class']} served "
                                        f"by tier {tier!r}")
        warm_s = time.perf_counter() - t
        c_warm1 = counters()
        if datanodes > 1:
            failures += mesh_check.problems(stack, datanodes)
        setup_s = time.perf_counter() - T0 - reference_s
        say(setup={"datagen_s": datagen_s, "load_s": load_s,
                   "load_by_table_s": load_by_table,
                   "reference_s_not_in_setup": reference_s,
                   "warm_s": warm_s, "setup_s": setup_s,
                   "warm_counters": {k: c_warm1[k] - c_warm0[k]
                                     for k in c_warm1},
                   "xla_total": dict(XLA)})

        # ---- the window -------------------------------------------------
        tiers0 = [dict(s.tier_counts) for _c, s in conns]
        fallbacks0 = [len(s.fallbacks) for _c, s in conns]
        trace_dir = os.path.join(run_dir, "trace")
        traced = bool(args.trace)
        trace_clock = {}

        def start_profile(_t_start):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_clock["t0"] = time.perf_counter()

            def stop():
                time.sleep(float(mix.spec.get("trace", {}).get(
                    "seconds", 5.0)))
                trace_clock["t1"] = time.perf_counter()
                jax.profiler.stop_trace()

            th = threading.Thread(target=stop, daemon=True)
            th.start()
            trace_clock["thread"] = th

        c0 = counters()
        requests, t_start, t_end = mix.drive(
            conns, args.seconds, traced=traced,
            annotate=jax.profiler.TraceAnnotation if traced else None,
            on_start=start_profile if traced else None)
        c1 = counters()
        if traced:
            trace_clock["thread"].join()

        # ---- correct: every reply of the window against the reference ---
        failed_steps = 0
        for req in requests:
            bad, a, u = mix.check(req, limits)
            avg_gap, ulp_gap = max(avg_gap, a), max(ulp_gap, u)
            failed_steps += len(bad)
            failures += bad
        attempted = sum(len(r.steps) for r in requests)
        unserved = 0
        for (_c, s), before in zip(conns, tiers0):
            for tier, n in s.tier_counts.items():
                if tier not in mix.served_tiers:
                    unserved += n - before.get(tier, 0)
        fallbacks = [f for (_c, s), n0 in zip(conns, fallbacks0)
                     for f in s.fallbacks[n0:]]
        if unserved:
            failures.append(f"{unserved} replies of the window served by a "
                            f"tier outside {sorted(mix.served_tiers)}")
        if fallbacks:
            failures.append(f"fallbacks in the window: {fallbacks[:3]}")
        compared = [
            {"number": "statements_failing_the_comparison",
             "value": failed_steps, "limit": 0},
            {"number": "avg_columns_widest_relative_gap",
             "value": avg_gap, "limit": limits["avg_rel_gap"]},
            {"number": "decimal_columns_widest_ulp_gap",
             "value": ulp_gap, "limit": limits["decimal_ulp_gap"]},
            {"number": "replies_from_unserved_tier", "value": unserved,
             "limit": 0},
            {"number": "fallbacks", "value": len(fallbacks), "limit": 0},
            {"number": "set_up_failures",
             "value": sum(1 for f in failures if f.startswith("warm-up")
                          or f.startswith("mesh")), "limit": 0},
        ]
        for c in compared:
            say(compared=c)
        for f in failures[:10]:
            say(failure=f)
        correct = not failures and attempted > 0

        # ---- metrics ----------------------------------------------------
        ctx = Context(mix, data, requests, failed_steps, t_start, t_end,
                      c0, c1, peaks)
        by_class = ctx.latencies_ms_by_class()
        say(window={"seconds": ctx.window_s, "statements": attempted,
                    "samples_by_class": {k: len(v)
                                         for k, v in by_class.items()},
                    "median_ms_by_class": {k: stats.median(v)
                                           for k, v in by_class.items()},
                    "counters": {k: c1[k] - c0[k] for k in c1}})
        device = dict(dev, memory_peak_bytes=memory_peak_bytes())
        metrics, breakdown = {}, None
        if not traced:
            for m in bench["end_to_end"]:
                if not applies(m, args.workload):
                    continue
                if m["name"] == "setup_s":
                    value = setup_s
                else:
                    value = read_metric(ctx, files.load_json(
                        "end_to_end", m["name"] + ".json"))
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            tr = profile.Trace.from_file(profile.find_xplane(trace_dir))
            lo, hi = profile.span_of(tr)
            ctx.trace = tr
            ctx.trace_window_s = trace_clock["t1"] - trace_clock["t0"]
            ctx.trace_busy_s = profile.busy_s_mean(tr)
            say(trace={"planes": tr.describe(),
                       "annotations": len(tr.annotations),
                       "span_s": (hi - lo) / 1e9,
                       "window_s": ctx.trace_window_s,
                       "busy_s": ctx.trace_busy_s})
            device.update(busy_s=ctx.trace_busy_s,
                          window_s=ctx.trace_window_s)
            breakdown = {"device_ops": profile.top_ops(tr),
                         "idle_gaps": profile.idle_gaps(tr, lo, hi)}
            specs = files.layer_metrics()
            for m in bench["per_layer"]:
                if not applies(m, args.workload):
                    continue
                value = read_metric(ctx, specs[m["name"]])
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(profile.find_xplane(trace_dir), os.path.join(
                    args.keep_trace,
                    f"{args.workload}.{args.seed}.xplane.pb"))
    finally:
        if stack is not None:
            stack.stop()
        # at SF1 the datadir (WAL, checkpoints) and the COPY files are GBs
        shutil.rmtree(run_dir, ignore_errors=True)

    result = {"correct": bool(correct and on_chip and not rehearsal),
              "attempted": attempted,
              "failed": failed_steps if correct else max(failed_steps, 1),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if rehearsal or not on_chip:
        say(rehearsal=f"every phase ran on {dev['platform']} x{dev['count']} "
            f"with correct={correct}; only a tpu run may report success")
    print(json.dumps(result), flush=True)
    sys.exit(0 if on_chip and not rehearsal else 1)


if __name__ == "__main__":
    main()
