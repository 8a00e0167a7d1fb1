"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell is a ``workloads`` entry of
``BENCHMARK.json``; its configuration, traffic mix, limits and metric
readers are files under ``bench/`` found by name, and so is the
configuration's family (``bench/families/``), which gives the program's
model configuration, the weights, the reference and the work counts.
A run:

1. turns on JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
2. makes the weights on the device from ``--seed``;
3. builds a ``DecodeEngine`` from the configuration file;
4. warms up every program shape the mix can reach (the segment, each
   admission bucket, batch-1 and pool-wide prefill, chunked ingest);
5. runs the mix for its ``warm_s`` so that the slots fill;
6. measures for ``--seconds`` (with ``--trace 1``, for the mix's
   ``trace_s`` under the profiler);
7. reads the device's peak memory, frees the engine, checks a sample of
   the finished requests against the plain reference, and prints the
   result.

The result records under ``setup_compile`` what set-up compiled and what
it found in the cache, so that a checkout's first run, which compiles,
can be told from the others.

It exits non-zero without a result when JAX finds no TPU or fewer chips
than the cell asks for, and when anything compiles inside the window.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench import check, program_trace, spec, stats  # noqa: E402
from bench.client import Client  # noqa: E402
from bench.traffic import Traffic, pow2_ceil  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
DRAIN_S = 90.0     # longest wait after the window for requests to finish
TRACE_DIR = ROOT / ".bench_trace"


class CompileCounter:
    """Backend compile seconds and programs, and persistent-cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        self.secs, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.programs += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class RunData:
    """What a run recorded, as the metric readers see it: the
    configuration (``conf``), the client's records, the window
    (``t0``, ``t1``), the window delta of every integer counter of the
    engine's stats (``counters``), and in a traced run the profiler
    trace reduced by ``bench/trace.py`` (``trace``) and by
    ``bench/program_trace.py`` (``program_trace``: the program's own
    spans and each device op's named scope); both are None untraced."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def work(self):
        """The configuration's operation and byte counts
        (``bench/work/<WORK>.py`` of its family)."""
        return spec.load_module("work", spec.family(self.conf).WORK)

    @property
    def peak(self) -> Dict[str, float]:
        """The chip's peaks (``bench/peaks.json``); an unknown device
        kind is an error, never a default."""
        table = spec.load_json(spec.BENCH_DIR / "peaks.json")
        if self.device_kind not in table:
            raise KeyError(f"device kind {self.device_kind!r} is not in "
                           "bench/peaks.json")
        return table[self.device_kind]


def use_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout whatever the environment says, and with no size limit that
    would evict a cell's programs, so that only a cell's first run in a
    checkout compiles."""
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_check(chips: int) -> Optional[str]:
    """Why this machine cannot run the cell, or None."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"needs a TPU; JAX found {devices[0].platform!r}"
    if len(devices) < chips:
        return f"needs {chips} chips; JAX found {len(devices)}"
    return None


def build_engine(conf: Dict[str, Any], params):
    from repro.serving.engine import DecodeEngine
    sv = conf["serving"]
    return DecodeEngine(
        params, spec.model_config(conf), n_slots=sv["n_slots"],
        segment_len=sv["segment_len"], max_len=sv["max_len"],
        prefill_chunk=sv["prefill_chunk"], admission="batched")


def warm_lengths(conf: Dict[str, Any], mix: Dict[str, Any]) -> List[int]:
    """Prompt lengths whose admission reaches every program shape the
    mix can: each first-chunk bucket, each continuation-chunk bucket,
    and (where the family's decode state grows with the context) each
    bucket of prompt length, which sizes the activation snapshot."""
    chunk = conf["serving"]["prefill_chunk"]
    pmin, pmax = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    lengths = set()
    w = pow2_ceil(min(pmin, chunk))
    while w <= pow2_ceil(min(pmax, chunk)):
        lengths.add(w)
        w *= 2
    if pmax > chunk:
        r = 1
        while r <= pow2_ceil(min(pmax - chunk, chunk)):
            lengths.add(chunk + r)
            r *= 2
    if spec.family(conf).grows_kv(conf):
        w = pow2_ceil(pmin)
        while w <= pow2_ceil(pmax):
            lengths.add(min(w, pmax))
            w *= 2
    return sorted(lengths)


def warm_up(engine, conf: Dict[str, Any], mix: Dict[str, Any]) -> int:
    """Admit each warm length twice in one wave (the pool-wide programs)
    and once alone (the batch-1 ones), each decoding through a segment;
    a length past the first chunk only once, as its first chunk is one
    already warmed. Returns how many requests it ran."""
    n, chunk = 0, conf["serving"]["prefill_chunk"]
    for length in warm_lengths(conf, mix):
        prompt = [1] * length
        for wave in ((2, 1) if length <= chunk else (1,)):
            for _ in range(wave):
                engine.submit(prompt, 2)
                n += 1
            while engine.step("continuous"):
                pass
    return n


def counters(engine) -> Dict[str, int]:
    """Every field of the engine's stats (a dataclass) declared ``int``,
    so that a counter the program adds reaches the readers."""
    st = engine.stats
    return {f.name: int(getattr(st, f.name)) for f in dataclasses.fields(st)
            if f.type in (int, "int")}


def measure(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            t_process: float = T_PROCESS,
            trace_dir: Optional[Path] = None):
    """Steps 2 to 6 of a run and the metrics. Returns the result line's
    object without ``correct`` and ``checks``, the weights, and the
    client's records; the engine is freed."""
    from bench import weights

    compiles = CompileCounter()
    conf, mix = cell.config, cell.traffic
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    params = weights.make_params(conf, seed)
    engine = build_engine(conf, params)
    t = time.perf_counter()
    n_warm = warm_up(engine, conf, mix)
    log(f"warm-up: {n_warm} requests, {time.perf_counter() - t:.1f} s, "
        f"{compiles.programs} programs compiled in "
        f"{compiles.secs:.1f} s, {compiles.cache_hits} cache hits")
    engine.reset()

    traffic = Traffic(mix, conf["vocab_size"], seed)
    client = Client(engine, traffic)
    client.start()
    client.run_until(client.t_start + float(mix["warm_s"]))
    window = float(mix["trace_s"]) if traced else float(seconds)
    trace_dir = Path(trace_dir or TRACE_DIR / f"{cell.name}.{seed}")
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    t0 = client.run_until(time.perf_counter())    # an observation
    setup_compile = {"programs": compiles.programs,
                     "seconds": compiles.secs,
                     "cache_hits": compiles.cache_hits}
    c0 = counters(engine)
    queued0 = engine.queue_depth()
    setup_s = t0 - t_process
    client.open_window(t0)
    t1 = client.run_until(t0 + window)
    client.close_window(t1)
    c1 = counters(engine)
    if traced:
        jax.profiler.stop_trace()
    client.drain(int(mix["check"]["requests"]), t1 + DRAIN_S)
    in_window = compiles.programs - setup_compile["programs"]
    if in_window:
        raise RuntimeError(f"{in_window} programs compiled inside the "
                           "window: the warm-up missed a shape")
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    delta = {k: c1[k] - c0[k] for k in c1}
    delta.update(n_slots=engine.n_slots, segment_len=engine.segment_len,
                 queued_at_open=queued0, queued_at_close=engine.queue_depth())
    log(f"window {t1 - t0:.3f} s; counters {json.dumps(delta)}")
    records = client.window_records()
    itl = sorted(stats.itl_samples(records))
    if itl:
        q = {p: stats.percentile(itl, p) * 1e3 for p in (50, 90, 95, 99)}
        log(f"inter-token latency over {len(itl)} tokens, ms: "
            + ", ".join(f"p{p} {v:.2f}" for p, v in q.items())
            + f", max {itl[-1] * 1e3:.2f}")
    attempted = client.attempted(t0, t1)
    failed = sum(r.status not in (None, "ok") for r in attempted)
    output_tokens = client.output_tokens()
    closed = traffic.closed
    del client, engine
    gc.collect()

    summary = program = None
    if traced:
        summary, program, _, _ = program_trace.load(trace_dir)
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        if trace_dir.parent == TRACE_DIR:
            shutil.rmtree(trace_dir, ignore_errors=True)

    run = RunData(conf=conf, cell=cell.name, records=records, t0=t0, t1=t1,
                  setup_s=setup_s, closed=closed, counters=delta,
                  output_tokens=output_tokens, trace=summary,
                  program_trace=program, device_kind=device["kind"])
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"attempted": len(attempted), "failed": failed,
              "metrics": metrics, "device": device, "counters": delta,
              "setup_compile": setup_compile}
    if summary is not None:
        result["breakdown"] = {
            "device_ops": summary.top_ops(10),
            "idle_gaps": program_trace.labelled_gaps(summary, program, 10)}
    return result, params, records


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_process: float = T_PROCESS,
             trace_dir: Optional[Path] = None) -> Dict[str, Any]:
    """One run of one cell; returns the result line's object."""
    result, params, records = measure(cell, seed, seconds, traced,
                                      t_process, trace_dir)
    conf = cell.config
    picked = check.sample(records, seed, cell.traffic["check"])
    t = time.perf_counter()
    numbers = check.compare(params, conf, picked, conf["vocab_size"])
    print(f"reference: {len(picked)} requests, "
          f"{int(numbers['served_tokens'])} served tokens, "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr, flush=True)
    out = {"correct": check.verdict(numbers, cell.limits), **result}
    out["checks"] = {k: {"value": numbers[k], "limit": v}
                     for k, v in cell.limits.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    why = device_check(cell.chips)
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    use_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      trace_dir=args.trace_dir)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
