"""Smoke run of the serving path on one TPU chip, at qwen3-0.6b width.

    python chip_smoke.py [--seed N]

One process runs four phases through the entry points a user calls,
with random weights made from ``--seed``:

1. serve, linear: ``serve.py --mode stream`` on qwen3-0.6b (published
   widths: 28 layers, d_model 1024, 16 heads / 8 KV heads, head_dim 128,
   vocab 151936) with the linear backend — 16 slots, 16 requests of 192
   prompt tokens, 32 generated, segments of 8, greedy. Every request
   must complete, and the compiled segment program must hold the Pallas
   decode kernels (``tpu_custom_call``).
2. fused against reference: ``lm.decode_window`` and
   ``lm.decode_window_varlen`` (staggered lengths) logits under
   ``decode_kernel="fused"`` and ``"reference"`` at the same width, in
   float32 at the highest matmul precision and in the served bfloat16.
   The fused program must hold the Pallas kernels and the reference
   program none.
3. serve, gated_linear and softmax: the same stream path, 8 requests
   each (the gated fused kernel and the KV-cache baseline).
4. lookup: ``serve.py --mode lookup`` over 4096 documents of 256
   tokens and 8192 queries in waves of 256; one kernel dispatch per
   wave, and one more wave checked against ``mass_lookup_indexed_ref``.

It exits non-zero before any phase when JAX finds no TPU, and at the
first failed check. The last line of standard output, printed only when
every phase passed, is one JSON object naming the device. Times printed
are smoke wall time, compilation included: this is not a benchmark. The
serving CLI's own report goes to standard error.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen3-0.6b"
ARCH_ARGS = ["--arch", ARCH]
STREAM_ARGS = ["--slots", "16", "--prompt-len", "192", "--gen-len", "32",
               "--segment-len", "8", "--prefill-chunk", "64",
               "--temperature", "0"]
LOOKUP_ARGS = ["--n-docs", "4096", "--doc-len", "256", "--n-queries",
               "8192", "--wave-size", "256", "--lookup-backend", "linear"]
# fused vs reference: largest |Δlogit| over the largest |reference
# logit|, at the positions each row consumed. float32 at the highest
# matmul precision leaves only the f32 summation order of the
# recurrence; bfloat16 also rounds every activation to 8 mantissa bits
# (step 2^-8 ≈ 3.9e-3), and a one-step flip can grow through 28 layers.
# The reasoning behind each value is in CHANGES.md.
TOL_F32 = 1e-3
TOL_BF16 = 6e-2
# lookup wave vs the float32 reference: the kernel's MXU dot may round
# its f32 operands to bfloat16 (relative step 2^-8) before summing k=64
# products.
TOL_LOOKUP = 1e-2


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

    def report(self, since=(0.0, 0, 0)):
        secs, programs, hits = since
        return (f"backend compile {self.secs - secs} s over "
                f"{self.programs - programs} programs; persistent-cache "
                f"hits {self.cache_hits - hits}")

    def snapshot(self):
        return self.secs, self.programs, self.cache_hits


def _check(ok, what):
    """A failed smoke check; raised, not asserted, so ``python -O``
    cannot skip it."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _config(backend):
    from repro.configs import get_config
    return get_config(ARCH).with_backend(backend)


def _phase(counter, name, fn, *args):
    before = counter.snapshot()
    t0 = time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    print(f"[{name}] smoke wall {wall} s incl. compile; "
          f"{counter.report(before)}", flush=True)
    return out


def _serve(argv):
    """Run the serving CLI in-process; return the engine it drove. Its
    report goes to stderr so stdout holds only this script's lines."""
    from repro.launch import serve
    engines = []
    with contextlib.redirect_stdout(sys.stderr):
        rc = serve.main(argv, engines)
    _check(rc == 0, f"serve.main{tuple(argv)} returned {rc}")
    _check(len(engines) == 1, f"{len(engines)} engines from {argv}")
    return engines[0]


def serve_stream(backend, n_requests, seed, expect_kernel):
    engine = _serve(["--mode", "stream", *ARCH_ARGS, "--backend", backend,
                     "--n-requests", str(n_requests), "--seed", str(seed),
                     *STREAM_ARGS])
    comps = engine.completions()
    statuses = sorted({c.status for c in comps})
    _check(len(comps) == n_requests and statuses == ["ok"],
           f"{backend}: {len(comps)} completions, statuses {statuses}")
    gen = int(STREAM_ARGS[STREAM_ARGS.index("--gen-len") + 1])
    # serve's request mix: every 4th request runs gen-len tokens, the
    # rest gen-len // 8 (no EOS is configured)
    want = [gen if i % 4 == 0 else max(1, gen // 8)
            for i in range(n_requests)]
    got = [len(c.tokens) for c in sorted(comps, key=lambda c: c.uid)]
    _check(got == want, f"{backend}: tokens per request {got} != {want}")
    vocab = engine.cfg.vocab_size
    _check(all(0 <= t < vocab for c in comps for t in c.tokens),
           f"{backend}: token id outside the vocabulary")
    kernels = "tpu_custom_call" in engine.segment_program_text()
    _check(expect_kernel is None or kernels == expect_kernel,
           f"{backend}: tpu_custom_call in segment program = {kernels}")
    st = engine.stats
    print(f"  {backend}: requests={len(comps)} statuses={statuses} "
          f"tokens={sum(got)} segments={st.segments} "
          f"admission_waves={st.admission_batches} "
          f"ingest_chunks={st.ingest_chunks} "
          f"segment_program_has_tpu_custom_call={kernels}")
    return engine


def fused_vs_reference(params, seed):
    from repro.models import lm
    from repro.sharding import Rules
    rules = Rules.null()
    cfg = _config("linear")
    b, t_prompt, w = 16, 64, 8
    k_prompt, k_window = jax.random.split(jax.random.PRNGKey(seed + 1))
    prompt = jax.random.randint(k_prompt, (b, t_prompt), 0, cfg.vocab_size)
    window = jax.random.randint(k_window, (b, w), 0, cfg.vocab_size)
    lens = jnp.arange(b, dtype=jnp.int32) % (w + 1)     # 0..w staggered
    pos0 = jnp.full((b,), t_prompt, jnp.int32)
    valid = np.arange(w)[None, :] < np.asarray(lens)[:, None]
    worst = {}
    for dtype, precision, tol in (("float32", "highest", TOL_F32),
                                  ("bfloat16", "default", TOL_BF16)):
        base = dataclasses.replace(cfg, dtype=dtype)
        with jax.default_matmul_precision(precision):
            _, state = jax.jit(
                lambda p, t: lm.prefill(p, t, base, rules))(params, prompt)
            out = {}
            for kernel in ("fused", "reference"):
                c = dataclasses.replace(base, decode_kernel=kernel)

                @jax.jit
                def windows(p, s, toks, c=c):
                    full, _ = lm.decode_window(p, s, toks, t_prompt, c,
                                               rules)
                    var, _ = lm.decode_window_varlen(p, s, toks, pos0, lens,
                                                     c, rules)
                    return (full.astype(jnp.float32),
                            var.astype(jnp.float32))

                compiled = windows.lower(params, state, window).compile()
                # the comparison means something only if the two sides
                # ran different code: the Pallas kernels, or none
                kernels = "tpu_custom_call" in compiled.as_text()
                _check(kernels == (kernel == "fused"),
                       f"{kernel} {dtype} windows: tpu_custom_call in "
                       f"program = {kernels}")
                out[kernel] = jax.device_get(compiled(params, state,
                                                      window))
        for i, name in enumerate(("decode_window", "decode_window_varlen")):
            f, r = out["fused"][i], out["reference"][i]
            if name == "decode_window_varlen":
                f, r = f[valid], r[valid]
            _check(np.isfinite(f).all() and np.isfinite(r).all(),
                   f"{name} {dtype}: non-finite logits")
            rel = float(np.max(np.abs(f - r)) / np.max(np.abs(r)))
            print(f"  {name} {dtype}: max|fused-ref| = "
                  f"{float(np.max(np.abs(f - r)))}, max|ref| = "
                  f"{float(np.max(np.abs(r)))}, relative {rel} "
                  f"(tolerance {tol})")
            _check(rel <= tol, f"{name} {dtype}: relative difference "
                   f"{rel} > {tol}")
            worst[(name, dtype)] = rel
    return worst


def lookup(seed):
    from repro.kernels.lookup.ref import mass_lookup_indexed_ref
    engine = _serve(["--mode", "lookup", "--seed", str(seed), *LOOKUP_ARGS])
    st = engine.stats
    n_docs = int(LOOKUP_ARGS[LOOKUP_ARGS.index("--n-docs") + 1])
    n_q = int(LOOKUP_ARGS[LOOKUP_ARGS.index("--n-queries") + 1])
    wave = int(LOOKUP_ARGS[LOOKUP_ARGS.index("--wave-size") + 1])
    _check(engine.backend.use_kernel, "lookup waves bypassed the kernel")
    _check(st.documents == n_docs, f"{st.documents} documents resident")
    # serve's lookup mode runs a warm storm and a served storm
    _check(st.queries == 2 * n_q, f"{st.queries} queries served")
    _check(st.lookup_dispatches == st.waves,
           f"{st.lookup_dispatches} dispatches for {st.waves} waves")

    # one more wave, answered by the engine and by the jnp reference
    rng = np.random.default_rng(seed)
    ids = list(engine.rows())
    pick = rng.integers(0, len(ids), size=wave)
    q = rng.standard_normal((wave, engine.k)).astype(np.float32)
    uids = [engine.submit(ids[i], q[j]) for j, i in enumerate(pick)]
    waves = st.waves
    engine.step()
    _check(st.waves == waves + 1 and st.lookup_dispatches == st.waves,
           "the checked wave was not one dispatch")
    got = {r.uid: r.answers for r in engine.results()}
    got = np.stack([got[u][0] for u in uids])
    rows = jnp.asarray([engine.rows()[ids[i]] for i in pick], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(mass_lookup_indexed_ref(
            engine.store["c"], rows, jnp.asarray(q)[:, None, :]))[:, 0]
    diff = float(np.max(np.abs(got - want)))
    rel = diff / float(np.max(np.abs(want)))
    print(f"  lookup: documents={st.documents} queries={st.queries} "
          f"waves={st.waves} dispatches={st.lookup_dispatches} "
          f"use_kernel={engine.backend.use_kernel}; checked wave of "
          f"{wave}: max|kernel-ref| = {diff}, relative {rel} "
          f"(tolerance {TOL_LOOKUP})")
    _check(rel <= TOL_LOOKUP, f"lookup relative difference {rel} > "
           f"{TOL_LOOKUP}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    counter = CompileCounter()
    t0 = time.perf_counter()

    engine = _phase(counter, "serve linear", serve_stream, "linear", 16,
                    args.seed, True)
    params = engine.params
    del engine
    _phase(counter, "fused vs reference", fused_vs_reference, params,
           args.seed)
    del params
    _phase(counter, "serve gated_linear", serve_stream, "gated_linear", 8,
           args.seed, True)
    _phase(counter, "serve softmax", serve_stream, "softmax", 8, args.seed,
           None)
    _phase(counter, "lookup", lookup, args.seed)

    print(f"all phases passed: smoke wall {time.perf_counter() - t0} s "
          f"incl. compile; {counter.report()}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
