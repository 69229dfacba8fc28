"""On-chip smoke test: serve full-width qwen3-0.6b on one TPU chip.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host with four chips

One chip: boots the push-mode HTTP server (``serve_http``: pool +
background driver + scheduler tick + asyncio front-end) for qwen3-0.6b
at its published widths with seeded random weights and the paged KV
layout, streams a few requests of different lengths through real
sockets, checks the event protocol, then holds the served greedy tokens
against a plain reference: the model's full-sequence forward over
prompt + generated tokens, in float32 at the highest matmul precision.
Last, the paged Pallas kernels run at the model's head widths against
their jnp oracles.

Four chips (``--four-chips``, and nothing else): the instance pool
carves instances onto disjoint chips; starcoder2-15b at its published
widths serves a few requests at tensor-parallel degree 4 in bfloat16
(about 8 GB of parameters per chip, more than one chip holds alone);
and a depth-cut starcoder2 at published widths gives the same
first-token logits at degree 4 as at degree 1.

Every phase raises on failure, so any failure exits non-zero. The last
line of standard output is one JSON object naming the device; it is
printed only when every phase passed. Without a TPU the script exits
non-zero before doing anything else: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: served prompts: lengths differ and stay inside the warm-up buckets;
#: prompt + completion fits serve_http's fixed max_seq of 128
PROMPT_LENS = (5, 12, 23, 31)
MAX_NEW = 16

#: Tolerance of the served path against the reference, as a fraction of
#: the reference's largest |logit|. The server keeps float32
#: activations, and on a TPU every product takes bfloat16 inputs (the
#: weights stored so, the activations rounded: relative error 2^-9,
#: as the default-precision float32 product had); 28 layers of that
#: leave the logits a few parts in a thousand off. A served greedy token
#: must score within this margin of the reference's best token: a
#: near-tie may flip, anything else is a bug.
SERVED_RTOL = 2e-2
#: degree 4 vs degree 1 at the highest precision differ only in the
#: order of the cross-chip sums
TP_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    """The chip this run measures; exits non-zero when there is none."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU present (JAX reports platform "
                 f"{dev.platform!r}); this script does not fall back")
    return dev


def peak_hbm() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return f"{stats['peak_bytes_in_use'] / 2**30:.3f} GiB"


def _n_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


# ------------------------------------------------------------ HTTP phase
def start_server(cfg, seed: int = 0):
    """serve_http for ``cfg`` on a daemon thread; returns (frontend,
    thread, errors) once the socket is bound."""
    from repro.launch.engine_serve import serve_http

    bound: list = []
    errors: list = []
    up = threading.Event()

    def ready(fe) -> None:
        bound.append(fe)
        up.set()

    def run() -> None:
        try:
            serve_http(models=[cfg.name], port=0, slo_ms=600_000.0,
                       max_instances=1, max_slots=len(PROMPT_LENS),
                       seed=seed, ready=ready,
                       configs={cfg.name: cfg})
        except BaseException as e:  # noqa: BLE001 - re-raised by caller
            errors.append(e)
            up.set()

    t = threading.Thread(target=run, name="serve-http", daemon=True)
    t.start()
    if not up.wait(timeout=900.0):
        raise TimeoutError("server did not come up within 900 s")
    if errors:
        raise RuntimeError("server failed to start") from errors[0]
    return bound[0], t, errors


def stop_server(fe, thread, errors) -> None:
    fe.close_threadsafe()
    thread.join(timeout=60.0)
    if thread.is_alive():
        raise RuntimeError("server thread did not stop")
    if errors:
        raise RuntimeError("server thread failed") from errors[0]


async def stream(port: int, model: str, prompt, max_new: int) -> list:
    """POST /v1/generate and collect every ndjson event of the stream."""
    from repro.serving.workload import _read_chunked_events

    body = json.dumps({"model": model, "prompt": [int(t) for t in prompt],
                       "max_new_tokens": max_new,
                       "slo_ms": 600_000.0}).encode()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            raise AssertionError(f"request refused: {status!r}")
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        return [ev async for ev in _read_chunked_events(reader)]
    finally:
        writer.close()
        await writer.wait_closed()


def check_events(events: list, max_new: int) -> list:
    """Event protocol: accepted -> prefill -> decode -> token* ->
    finished, token indices 0..n-1 in order, and the finished event's
    tokens equal the streamed ones. Returns the tokens."""
    kinds = [e["event"] for e in events]
    if kinds[0] != "accepted" or kinds[-1] != "finished":
        raise AssertionError(f"stream did not finish: {kinds}")
    for phase in ("prefill", "decode"):
        if phase not in kinds:
            raise AssertionError(f"no {phase!r} event: {kinds}")
    if kinds.index("prefill") > kinds.index("token"):
        raise AssertionError(f"token before prefill: {kinds}")
    toks = [e for e in events if e["event"] == "token"]
    if [e["index"] for e in toks] != list(range(max_new)):
        raise AssertionError(
            f"token indices {[e['index'] for e in toks]}, want 0..{max_new-1}")
    streamed = [e["token"] for e in toks]
    if events[-1]["tokens"] != streamed:
        raise AssertionError("finished tokens differ from the stream")
    return streamed


def serve_and_check(cfg, seed: int = 0) -> dict:
    """Boot the server, stream the prompts, check the protocol, stop the
    server. Returns prompts, served tokens and the served engine."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS]
    t0 = time.perf_counter()
    fe, thread, errors = start_server(cfg, seed)
    boot_s = time.perf_counter() - t0
    log(f"boot (params init + compile + warm-up): {boot_s:.1f} s")

    async def run_all():
        return await asyncio.gather(*(
            stream(fe.port, cfg.name, p, MAX_NEW) for p in prompts))

    t0 = time.perf_counter()
    all_events = asyncio.run(run_all())
    serve_s = time.perf_counter() - t0
    served = [check_events(ev, MAX_NEW) for ev in all_events]
    log(f"requests answered over HTTP: {len(served)}/{len(prompts)} "
        f"finished, {MAX_NEW} tokens each, prompt lengths {PROMPT_LENS}, "
        f"{serve_s:.2f} s wall for the batch")
    stop_server(fe, thread, errors)
    inst = fe.driver.pool.live(cfg.name)[0]
    return {"prompts": prompts, "served": served, "engine": inst.engine}


# ------------------------------------------------------- reference phase
def check_against_reference(cfg, engine, prompts, served) -> None:
    """Teacher-forced reference over prompt + served tokens (float32,
    highest precision, plain attention) on the served weights' values,
    widened to float32 whatever type the engine stores them in. The
    engine serves each prompt left-padded with token 0 to its length
    bucket and attends the pad rows, so the reference reads that same
    padded prompt."""
    from repro.models import build_model

    ref = build_model(cfg, remat=False, attn_impl="naive",
                      compute_dtype=jnp.float32)
    prompts = [engine._padded_seq(p) for p in prompts]
    lens = [len(p) + len(s) for p, s in zip(prompts, served)]
    S = max(lens)
    # right padding never reaches a causal position before it
    seqs = np.zeros((len(prompts), S), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        seqs[i, :lens[i]] = np.concatenate([p, np.asarray(s, np.int32)])
    with jax.default_matmul_precision("highest"):
        ref_logits = np.asarray(
            jax.jit(ref.logits)(engine.params, {"tokens": seqs}),
            np.float32)
    scale = float(np.abs(ref_logits).max())

    # first-token logits of the serving model at serving precision
    p0 = prompts[0]
    served0, _ = jax.jit(engine.model.prefill)(engine.params,
                                               {"tokens": p0[None]})
    served0 = np.asarray(served0, np.float32)[0, -1]
    ref0 = ref_logits[0, len(p0) - 1]
    err = float(np.abs(served0 - ref0).max())
    log(f"first-token logits: max |served - reference| = {err:.3e}, "
        f"max |reference| = {scale:.3e}, tolerance {SERVED_RTOL:g} x "
        f"max |reference| = {SERVED_RTOL * scale:.3e}")
    if not np.isfinite(served0).all() or err > SERVED_RTOL * scale:
        raise AssertionError("first-token logits off the reference")

    agree = total = 0
    worst = 0.0
    for i, (p, s) in enumerate(zip(prompts, served)):
        rows = ref_logits[i, len(p) - 1:len(p) - 1 + len(s)]
        agree += int((rows.argmax(-1) == np.asarray(s)).sum())
        total += len(s)
        # how far each served token scores below the reference's best
        gap = rows.max(-1) - rows[np.arange(len(s)), s]
        worst = max(worst, float(gap.max()))
    log(f"greedy token agreement with the reference: {agree}/{total}; "
        f"largest reference-logit gap of a served token {worst:.3e} "
        f"(tolerance {SERVED_RTOL * scale:.3e}: near-ties may flip)")
    if worst > SERVED_RTOL * scale:
        raise AssertionError("a served token is not the reference's "
                             "greedy choice within tolerance")


# -------------------------------------------------------- kernel phase
#: The paged kernels against their oracles, as a fraction of the
#: oracle's largest |value|: room for bfloat16 storage (relative
#: rounding 2^-9) and for one bfloat16 pass of the matrix unit. A wrong
#: block, head or mask is off by the size of the values themselves.
KERNEL_RTOL = 2e-2


def paged_kernels_match_oracle(cfg, seed: int = 0) -> None:
    """The three paged Pallas kernels, compiled for the device, against
    their jnp oracles (float32, highest precision) at ``cfg``'s head
    widths: ragged lengths, a shuffled block table, float32 and
    bfloat16 pools. Serving does not call these kernels yet; this shows
    that they run right on the chip, not only that they compile."""
    from repro.kernels import ops, ref

    B, bs, nb, T = 4, 16, 8, 24
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(1 + rng.permutation(B * nb).reshape(B, nb),
                         jnp.int32)
    lens = jnp.asarray([1, bs, 3 * bs + 5, nb * bs], jnp.int32)
    pos = jnp.maximum(lens - T, 0)       # chunk rows pos .. pos+T-1
    scale = hd ** -0.5
    kernels = {
        "paged_decode": (ops.paged_decode_attention,
                         ref.paged_decode_attention_ref, 1, lens),
        "paged_decode_splitk": (ops.paged_decode_attention_splitk,
                                ref.paged_decode_attention_splitk_ref, 1,
                                lens),
        "paged_prefill": (ops.paged_prefill_attention,
                          ref.paged_prefill_attention_ref, T, pos),
    }
    for dtype in (jnp.float32, jnp.bfloat16):
        k, v = (jnp.asarray(rng.standard_normal((B * nb + 1, bs, KV, hd)),
                            dtype) for _ in range(2))
        for name, (kernel, oracle, rows, extent) in kernels.items():
            q = jnp.asarray(rng.standard_normal((B, rows, H, hd)), dtype)
            got = np.asarray(kernel(q, k, v, tables, extent, scale),
                             np.float32)
            with jax.default_matmul_precision("highest"):
                want = np.asarray(oracle(
                    *(x.astype(jnp.float32) for x in (q, k, v)), tables,
                    extent, scale))
            err = float(np.abs(got - want).max())
            tol = KERNEL_RTOL * float(np.abs(want).max())
            log(f"kernel {name} {jnp.dtype(dtype).name}: max |kernel - "
                f"oracle| = {err:.3e}, tolerance {tol:.3e}")
            if not np.isfinite(got).all() or err > tol:
                raise AssertionError(f"{name} off its oracle")


def one_chip(cfg, seed: int = 0) -> None:
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, head_dim {cfg.head_dim}, "
        f"vocab {cfg.vocab_size}, float32, paged KV")
    out = serve_and_check(cfg, seed)
    log(f"parameters: {_n_params(out['engine'].params) / 1e9:.3f} B")
    check_against_reference(cfg, out["engine"], out["prompts"],
                            out["served"])
    log(f"peak HBM: {peak_hbm()}")
    paged_kernels_match_oracle(cfg, seed)


# ------------------------------------------------------ four-chip phase
def _param_devices(params) -> list:
    """Ids of the devices holding any shard of ``params``."""
    return sorted({d.id for x in jax.tree.leaves(params)
                   for d in x.devices()})


def pool_placement(cfg, n_devices: int = 4) -> None:
    """Two tp=2 instances on two chip pairs, then n_devices tp=1
    instances on n_devices chips."""
    from repro.serving.runtime import ModelInstancePool

    pool = ModelInstancePool({cfg.name: cfg}, max_instances=n_devices,
                             max_slots=2, max_seq=128,
                             tp_degree=2, n_devices=n_devices)
    for tp, m_c in ((2, n_devices // 2), (1, n_devices)):
        pool.set_tp_degree(cfg.name, tp)
        pool.step()  # the sweep retires the other degree's instances
        if pool.scale_to(cfg.name, m_c) != m_c:
            raise AssertionError(f"pool reached fewer than {m_c} "
                                 f"tp={tp} instances")
        sets = [_param_devices(i.engine.params) for i in pool.live(cfg.name)]
        for i, ids in zip(pool.live(cfg.name), sets):
            log(f"pool tp={tp} instance {i.instance_id}: params on "
                f"devices {ids}")
        flat = [d for ids in sets for d in ids]
        if any(len(ids) != tp for ids in sets) or len(set(flat)) != len(flat):
            raise AssertionError(f"tp={tp} instances overlap: {sets}")


def sharded_serving(cfg, tp: int = 4) -> None:
    """Full-width cfg at degree ``tp`` in bfloat16 answers requests."""
    from repro.launch.mesh import make_tp_mesh
    from repro.serving.engine import ContinuousBatchingEngine

    t0 = time.perf_counter()
    eng = ContinuousBatchingEngine(cfg, max_slots=2, max_seq=128,
                                   dtype=jnp.bfloat16, seed=0,
                                   mesh=make_tp_mesh(tp))
    per_dev: dict = {}
    for leaf in jax.tree.leaves(eng.params):
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] = per_dev.get(shard.device.id, 0) \
                + shard.data.nbytes
    log(f"{cfg.name} at tp={tp}, bfloat16: "
        f"{_n_params(eng.params) / 1e9:.3f} B parameters, per device "
        + ", ".join(f"{d}: {b / 1e9:.3f} GB" for d, b in
                    sorted(per_dev.items()))
        + f" (init {time.perf_counter() - t0:.1f} s)")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (7, 19)]
    t0 = time.perf_counter()
    res = eng.run(prompts, max_new_tokens=8)
    if len(res) != len(prompts) or any(len(r.tokens) != 8 for r in res):
        raise AssertionError(f"{cfg.name} tp={tp} did not answer")
    log(f"{cfg.name} tp={tp} answered {len(res)} requests, 8 tokens "
        f"each, in {time.perf_counter() - t0:.1f} s (compile included)")


def tp_matches_single_chip(cfg, tp: int = 4) -> None:
    """First-token logits and greedy tokens of a depth-cut cfg at degree
    tp vs degree 1, both with float32 weights at the highest matmul
    precision, so the two differ only in the order of their sums."""
    from repro.launch.mesh import make_tp_mesh
    from repro.serving.engine import ContinuousBatchingEngine

    prompt = np.random.default_rng(2).integers(
        1, cfg.vocab_size, 24).astype(np.int32)
    logits, toks = [], []
    for mesh in (None, make_tp_mesh(tp)):
        eng = ContinuousBatchingEngine(cfg, max_slots=1, max_seq=128,
                                       seed=0, mesh=mesh,
                                       weight_dtype=jnp.float32)
        with jax.default_matmul_precision("highest"):
            lg, _ = jax.jit(eng.model.prefill)(eng.params,
                                               {"tokens": prompt[None]})
            toks.append([int(t) for t in
                         eng.run([prompt], max_new_tokens=4)[0].tokens])
        logits.append(np.asarray(lg, np.float32)[0, -1])
    err = float(np.abs(logits[0] - logits[1]).max())
    scale = float(np.abs(logits[0]).max())
    log(f"{cfg.name} ({cfg.n_layers} layers, published widths) tp={tp} vs "
        f"tp=1 first-token logits: max |diff| = {err:.3e}, tolerance "
        f"{TP_RTOL:g} x max |logit| = {TP_RTOL * scale:.3e}; greedy "
        f"tokens {toks[1]} vs {toks[0]}")
    if err > TP_RTOL * scale:
        raise AssertionError(f"tp={tp} logits off tp=1")
    if toks[0] != toks[1]:
        raise AssertionError(f"tp={tp} greedy tokens differ from tp=1")


def four_chips() -> None:
    import dataclasses

    from repro.config import get_config

    n = len(jax.devices())
    if n < 4:
        sys.exit(f"chip_smoke --four-chips: needs 4 chips, JAX sees {n}")
    pool_placement(get_config("qwen3-0.6b"))
    gc.collect()
    sc2 = get_config("starcoder2-15b")
    sharded_serving(sc2)
    gc.collect()
    tp_matches_single_chip(dataclasses.replace(sc2, n_layers=2))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (pool placement, "
                         "starcoder2-15b at tp=4, tp=4 vs tp=1 logits)")
    args = ap.parse_args()
    dev = require_tpu()

    from repro.config import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.device_kind} x {len(jax.devices())}; compile "
        f"cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips()
    else:
        one_chip(get_config("qwen3-0.6b"))
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
