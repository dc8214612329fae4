#!/usr/bin/env python3
"""Benchmark harness: one cell of ``BENCHMARK.json``, one process.

    python3 bench/run.py --workload fraud.busy --seed 7 --seconds 30 --trace 0

The cell names a configuration (``bench/configs/<config>.json``: the
deployment and the policy it is served under) and a traffic mix
(``bench/traffic/<mix>.json``, read by ``bench/generate.py``).  A run

1. checks for a TPU with as many chips as the cell asks for, whose kind is
   in ``bench/peaks.json`` (no result and a non-zero exit otherwise);
2. builds the service with ``repro.serve.build_service``, its plan and
   executable caches at a fixed path under the checkout's ``out/``;
3. generates the seed's event pool;
4. serves two chunks through the window's own entry (the first and the
   steady program), which ends the set-up (``setup_s``);
5. runs the window: backlogged mixes through ``ServeLoop.serve``, mixes
   with an open-loop schedule chunk by chunk through ``ServeLoop.step``
   as each chunk falls due;
   every result is emitted to the host (``jax.device_get``);
6. compares the emitted outputs of the sampled keys with the plain
   reference (``bench/refs/<app>.py``, float64), over the whole stream;
7. prints the checks on standard error and, as the last line of standard
   output, one JSON object.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, each read by ``bench/metrics/<metric>.py``
(or, for a metric ``<name>.<split>`` with no file of its own, by
``bench/metrics/<name>.py``).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, "out", "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402
import generate  # noqa: E402
import plugins  # noqa: E402
import trace as trace_reduce  # noqa: E402
import window  # noqa: E402

WARMUP_CHUNKS = 2   # the first-chunk program and the steady one


# -- the pieces of a cell, found by name ---------------------------------------

def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT, "BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(BENCH, "configs", f"{name}.json")


def traffic(name: str) -> dict:
    return _json(BENCH, "traffic", f"{name}.json")


def peaks() -> dict:
    return _json(BENCH, "peaks.json")["devices"]


def reference(app: str):
    return importlib.import_module(f"refs.{app}")


def reader(metric: str):
    """The per-layer metric's reader: ``bench/metrics/<metric>.py``, or
    for ``<name>.<split>`` (one quantity split by the end-to-end metric
    it moves) ``bench/metrics/<name>.py`` where the split has no file."""
    try:
        return plugins.load("metrics", metric)
    except FileNotFoundError:
        if "." not in metric:
            raise
        return plugins.load("metrics", metric.rsplit(".", 1)[0])


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def metrics_of(bm: dict, cell: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or (traced) its per-layer ones."""
    e2e = [m for m in bm["end_to_end"] if _applies(m, cell)]
    if not traced:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if m["moves"] in reported and _applies(m, cell)]


# -- devices --------------------------------------------------------------------

def require_devices(chips: int, table: dict):
    """The cell's TPU chips; exits (no result) when they are not there."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPU chips, "
                         f"found {len(devs)}")
    if devs[0].device_kind not in table:
        raise SystemExit(f"bench: device kind {devs[0].device_kind!r} is "
                         "not in bench/peaks.json")
    return devs[:chips]


def memory_peak(devices) -> int:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_))


# -- the served system ------------------------------------------------------------

def build(cfg: dict, devices):
    """The service the cell runs, warm from its caches where they hold
    its executables."""
    from jax.sharding import Mesh

    from repro.data.apps import make_keyed_app
    from repro.engine import ExecPolicy
    from repro.serve import build_service
    app = make_keyed_app(cfg["app"], **cfg["app_args"])
    pol = cfg["policy"]
    placement = (Mesh(np.asarray(devices), ("data",))
                 if pol["placement"] == "mesh" else "local")
    policy = ExecPolicy(body=pol["body"], keys=pol["keys"],
                        placement=placement)
    return build_service(app.query, out_len=cfg["seg"], policy=policy,
                         n_keys=cfg["keys"],
                         segs_per_chunk=cfg["segs_per_chunk"],
                         cache_dir=os.path.join(OUT, "serve", cfg["name"]))


class Stream:
    """The served stream: chunk ``c`` is pool chunk ``c % P`` at clock
    ``c * span``; emitted outputs of the sampled keys are kept."""

    def __init__(self, svc, pool, sample, spans):
        from repro.core.stream import SnapshotGrid
        self._grid = SnapshotGrid
        (self.name,) = svc.runner.spec.input_specs
        self.pool, self.sample, self.spans = pool, sample, spans
        self.next_chunk = 0
        self.kept_value, self.kept_valid = [], []

    def chunk(self):
        c = self.next_chunk
        self.next_chunk += 1
        p = c % self.pool.chunks
        with self.spans("bench.source"):
            return {self.name: self._grid(
                value=self.pool.value[p], valid=self.pool.valid[p],
                t0=c * self.pool.span, prec=1)}

    def emit(self, out):
        """The result on the host, as every sink needs it."""
        import jax
        with self.spans("bench.egress"):
            value, valid = jax.device_get((out.value, out.valid))
        self.kept_value.append(value[self.sample])
        self.kept_valid.append(valid[self.sample])

    def emitted(self):
        return (np.concatenate(self.kept_value, axis=1),
                np.concatenate(self.kept_valid, axis=1))


def run_backlogged(svc, stream, seconds, spans, clock=time.perf_counter):
    """``ServeLoop.serve`` over the endless stream until the first chunk
    that completes after ``seconds``.  Per chunk: when the loop was
    resumed for it, when its result was complete, when it was emitted."""
    def source():
        while True:
            yield stream.chunk()

    recs = []
    first = stream.next_chunk
    t_open = clock()
    served = svc.serve(source())
    with spans("bench.window"):
        while True:
            t_call = clock()
            with spans("bench.serve"):
                out = next(served)
            t_done = clock()
            stream.emit(out)
            t_emit = clock()
            recs.append({"call": t_call, "done": t_done, "emit": t_emit,
                         "events": stream.pool.events(first + len(recs))})
            if t_emit - t_open >= seconds:
                break
    served.close()
    return t_open, recs


def run_paced(svc, stream, schedule, spans, clock=time.perf_counter,
              sleep=time.sleep):
    """Open loop: chunk ``k`` is served by ``ServeLoop.step`` once its last
    tick is due (or as soon as the previous one is emitted, if later).
    ``schedule``: ``(chunk due (n,), tick due (n, span))`` in seconds
    after the window opens."""
    chunk_due, tick_due = schedule
    n = len(chunk_due)
    first = stream.next_chunk
    recs = []
    t_open = clock()
    with spans("bench.window"):
        for k in range(n):
            due = t_open + chunk_due[k]
            wait = due - clock()
            if wait > 0:
                with spans("bench.wait"):
                    sleep(wait)
            chunk = stream.chunk()
            t_call = clock()
            with spans("bench.step"):
                out = svc.step(chunk)
            t_done = clock()
            stream.emit(out)
            t_emit = clock()
            recs.append({"due": due, "call": t_call, "done": t_done,
                         "emit": t_emit,
                         "events": stream.pool.events(first + k)})
    return t_open, recs


def serve_chunks(svc, stream, n: int, paced: bool):
    """``n`` chunks through the window's own entry, each emitted."""
    if paced:
        for _ in range(n):
            stream.emit(svc.step(stream.chunk()))
    else:
        for out in svc.serve(stream.chunk() for _ in range(n)):
            stream.emit(out)


def sample_keys(cfg: dict, seed: int) -> np.ndarray:
    """The keys whose emitted outputs are compared, drawn from the seed."""
    rng = np.random.default_rng([seed, 1])
    return np.sort(rng.choice(cfg["keys"], cfg["sample_keys"],
                              replace=False))


def compare_stream(cfg: dict, stream) -> dict:
    """Readings of the comparison of every served chunk's emitted outputs
    (the sampled keys) with the plain reference over the same stream."""
    got_value, got_valid = stream.emitted()
    value, valid = generate.stream_rows(stream.pool, stream.sample,
                                        len(stream.kept_value))
    ref = reference(cfg["app"]).reference(value, valid, **cfg["app_args"])
    return check.compare(got_value, got_valid, ref,
                         cfg["limits"]["value_gap"])


def tick_events(pool, first: int, n: int) -> np.ndarray:
    """Valid events per tick ``(n, span)`` of window chunks ``first..``."""
    per = pool.valid.sum(axis=1)                  # (P, span)
    return per[(first + np.arange(n)) % pool.chunks]


# -- one run ----------------------------------------------------------------------

def run_cell(cell: str, seed: int, seconds: float, traced: bool, devices,
             bm=None) -> dict:
    """One run of ``cell`` on ``devices``: the result line as a dict."""
    import jax
    bm = bm if bm is not None else benchmark()
    wl = workload(bm, cell)
    cfg, mix = config(wl["config"]), traffic(wl["traffic"])
    span = cfg["seg"] * cfg["segs_per_chunk"]

    svc = build(cfg, devices)
    pool = generate.make_pool(mix, cfg["keys"], span, seed)
    schedule = generate.schedule(mix["pacing"], span, seconds)
    paced = schedule is not None
    trace_dir = os.path.join(OUT, "trace", cell)
    spans = _annotations if traced else _no_spans
    stream = Stream(svc, pool, sample_keys(cfg, seed), spans)
    for _ in range(WARMUP_CHUNKS):
        serve_chunks(svc, stream, 1, paced)
    setup_s = time.perf_counter() - T_PROCESS

    counters0 = _counters(svc) if traced else None
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans only: bench.*
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    first = stream.next_chunk
    if paced:
        t_open, recs = run_paced(svc, stream, schedule, spans)
    else:
        t_open, recs = run_backlogged(svc, stream, seconds, spans)
    if traced:
        jax.profiler.stop_trace()
    mem = memory_peak(devices)
    counters1 = _counters(svc) if traced else None

    # measurements
    e2e = {}
    if paced:
        emitted = np.asarray([r["emit"] - t_open for r in recs])
        lat = window.event_latencies(schedule[1][:len(recs)], emitted)
        w = tick_events(pool, first, len(recs))
        e2e["latency_p50_ms"] = 1e3 * window.weighted_percentile(lat, w, 50)
        e2e["latency_p95_ms"] = 1e3 * window.weighted_percentile(lat, w, 95)
    else:
        rate, _, _ = window.events_per_s(
            t_open, [r["emit"] for r in recs], [r["events"] for r in recs],
            seconds)
        e2e["events_per_s"] = rate
    e2e["setup_s"] = setup_s
    for r in recs:
        r.update({k: r[k] - t_open for k in ("due", "call", "done", "emit")
                  if k in r})

    # correctness, after the window: every served chunk of the sample
    readings = compare_stream(cfg, stream)
    correct, shown = check.verdict(readings, cfg["limits"])

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": len(recs), "failed": 0}
    if traced:
        summary = trace_reduce.summarize(trace_reduce.find_xplane(trace_dir),
                                         [d.id for d in devices])
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
        ctx = types.SimpleNamespace(
            cell=wl, config=cfg, traffic=mix, peak=peaks()[dev["kind"]],
            chunks=recs, trace=summary, chips=len(devices),
            counters={k: counters1[k] - counters0[k] for k in counters1})
        metrics = {}
        for m in metrics_of(bm, cell, True):
            v = reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["breakdown"] = summary["breakdown"]
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in metrics_of(bm, cell, False)}
    out["metrics"] = metrics
    out["device"] = dev
    out["readings"] = readings
    out["checks"] = shown
    return out


def _counters(svc) -> dict:
    snap = svc.runner.metrics.snapshot()["counters"]
    return {k: v["value"] for k, v in snap.items()}


@contextlib.contextmanager
def _no_spans(name):
    yield


def _annotations(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


# -- entry point ------------------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    bm = benchmark()
    wl = workload(bm, args.workload)
    # jax's persistent compilation cache lives at one fixed path in the
    # checkout (set before jax is imported, so the program takes it too)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(OUT, "jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    devices = require_devices(wl["chips"], peaks())
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices, bm=bm)
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
