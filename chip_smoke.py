"""Chip smoke test: the collaborative serving path on one TPU at
stablelm-1.6b's published widths (24 layers, d_model 2048, 32 heads,
d_ff 5632, vocab 100352; random weights drawn from ``--seed``).

    python chip_smoke.py [--seed N]

Everything runs in this one process; it starts no children.  Phases:

  (a) device   JAX must find a TPU and ``kernels.ops`` must pick the
               compiled Pallas kernels; anything else fails here.
  (b) kernels  each main-path kernel at stablelm-1.6b widths against its
               jnp reference in ``repro.kernels.ref``, with the tolerances
               derived below.
  (c) serve    the engine built as ``python -m repro.launch.serve`` builds
               it, one configuration phase, then 12 prompts of 128 tokens
               decoding 16 tokens each: with dense slot caches, with paged
               ones, and once more paged with the exit thresholds lowered so
               the early-exit branches retire rows mid-generation.  The
               compiled stage-decode programs and exit heads must hold the
               Pallas kernels (``tpu_custom_call``).
  (d) report   compile and serve seconds, peak device memory, tokens,
               exit histograms, dense/paged token agreement.

Any failed check raises and exits non-zero.  Only a run in which every
phase passed prints, as its last line, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from time import perf_counter

N_PROMPTS = 12
PROMPT_LEN = 128  # one length: one prefill program per stage and batch size
GEN_LEN = 16
BATCH = 8
ARRIVAL_RATE = 1e3  # simulated requests/s: prompts queue, so batches form
U32 = 2.0**-24  # f32 unit roundoff
U16 = 2.0**-9  # bf16 unit roundoff


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums JAX's own trace, lowering and backend-compile durations."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.programs += event == self.EVENTS[-1]


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def device_phase() -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    log(f"(a) device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    check(dev.platform == "tpu",
          f"no TPU: JAX found platform {dev.platform!r}; this smoke test "
          "runs only on a TPU")
    from repro.kernels import ops

    check(ops.get_backend() == "pallas",
          f"kernels.ops picked {ops.get_backend()!r}, not the compiled kernels")
    return info


# ---------------------------------------------------------------------------
# (b) kernels against their references
# ---------------------------------------------------------------------------


def check_exit_confidence(cfg, key, B: int) -> str:
    """Tolerances.  Kernel and reference multiply the same bf16 operands
    exactly and accumulate in f32, in different orders.  Re-association of
    a d-term sum moves a logit by at most d * u32 * sum_i |h_i w_iv|
    (the gamma_d bound), ``err`` per row below.  So:
      * each kernel token must be a top logit within 2 * err;
      * conf = 1 / sum_v exp(l_v - l_max) moves by at most 2 * err from the
        logits plus (V + 8) * u32 from summing V terms in another order and
        a few ulps of exp: rtol = 2 * err + (V + 8) * u32.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    d, V = cfg.d_model, cfg.vocab_size
    kh, kw = jax.random.split(key)
    h = jax.random.normal(kh, (B, d), cfg.dtype)
    # the serving path hands the head over in the compute dtype
    w = (jax.random.normal(kw, (d, V), jnp.float32) / d**0.5).astype(cfg.dtype)
    conf, tok = ops.exit_confidence(h, w)
    want_conf, _ = ref.exit_confidence_ref(h, w)
    wb = w.astype(h.dtype)
    logits = jnp.matmul(h, wb, preferred_element_type=jnp.float32)
    err = d * U32 * jnp.max(
        jnp.matmul(jnp.abs(h), jnp.abs(wb), preferred_element_type=jnp.float32),
        axis=-1,
    )
    top = jnp.max(logits, axis=-1)
    tok_logit = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    rtol = 2 * err + (V + 8) * U32
    rel = jnp.abs(conf - want_conf) / want_conf
    check(bool(jnp.all(tok_logit >= top - 2 * err)),
          f"exit_confidence B={B}: a token is not a top logit")
    check(bool(jnp.all(rel <= rtol)),
          f"exit_confidence B={B}: conf off by {float(jnp.max(rel)):.3g} "
          "relative, over its tolerance")
    flips = int(jnp.sum(tok_logit < top))
    return (f"exit_confidence B={B}: max rel conf diff {float(jnp.max(rel)):.3g}, "
            f"largest diff/tolerance {float(jnp.max(rel / rtol)):.3g}; "
            f"{flips} near-tie tokens differ from the f32 argmax")


def _attention_tolerance(q, k, v, lengths, want):
    """Per-element tolerance of a decode-attention output.

    Both sides round the softmax weights to bf16 (the kernel unnormalised
    inside its walk, the reference after normalising) and round the output
    to bf16: together 2 * u16 of the weighted mean |v| plus 2 * u16 of the
    output.  The reference also rounds the raw scores q.k to bf16 before
    scaling, an error of u16 * s_max on a scaled score, which moves each
    normalised weight by up to 2 * u16 * s_max relative; the tolerance takes
    twice that.  ``attn(|v|)``, the reference run on |v|, is the weighted
    mean |v|:

        tol = (2 * u16 + 4 * u16 * s_max) * attn(|v|) + 2 * u16 * |want|
    """
    import jax.numpy as jnp

    from repro.kernels import ref

    B, Hq, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(B, kvh, Hq // kvh, hd).astype(jnp.float32)
    s_max = jnp.max(jnp.abs(jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32))))
    s_max = s_max / hd**0.5
    tau = 2 * U16 + 4 * U16 * s_max
    mean_abs_v = ref.decode_attention_ref(q, k, jnp.abs(v), lengths)
    return (tau * mean_abs_v.astype(jnp.float32)
            + 2 * U16 * jnp.abs(want.astype(jnp.float32)))


def _compare_attention(name, got, want, tol) -> str:
    import jax.numpy as jnp

    diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    check(bool(jnp.all(jnp.isfinite(got))), f"{name}: non-finite output")
    check(bool(jnp.all(diff <= tol)),
          f"{name}: max diff {float(jnp.max(diff)):.3g} over its tolerance")
    return (f"{name}: max abs diff {float(jnp.max(diff)):.3g}, largest "
            f"diff/tolerance {float(jnp.max(diff / tol)):.3g}")


def check_decode_attention(cfg, key, B: int = 8, S: int = 1024) -> str:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv, kl = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, H, hd), cfg.dtype)
    k = jax.random.normal(kk, (B, S, KVH, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, KVH, hd), jnp.bfloat16)
    lengths = jax.random.randint(kl, (B,), 1, S + 1)
    got = ops.decode_attention(q, k, v, lengths)
    want = ref.decode_attention_ref(q, k, v, lengths)
    tol = _attention_tolerance(q, k, v, lengths, want)
    return _compare_attention(f"decode_attention B={B} S={S}", got, want, tol)


def check_paged_decode_attention(cfg, key, B: int = 8, S: int = 1024,
                                 block_size: int = 16) -> str:
    """Dense rows scattered into a shuffled block pool; the reference
    gathers them back, so the same tolerance applies."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n_logical = S // block_size
    kq, kk, kv, kl, kp = jax.random.split(key, 5)
    q = jax.random.normal(kq, (B, H, hd), cfg.dtype)
    k = jax.random.normal(kk, (B, S, KVH, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, KVH, hd), jnp.bfloat16)
    lengths = jax.random.randint(kl, (B,), 1, S + 1)
    perm = jax.random.permutation(kp, B * n_logical)
    table = perm.reshape(B, n_logical).astype(jnp.int32)
    pool_shape = (B * n_logical, block_size, KVH, hd)
    k_pool = jnp.zeros(pool_shape, k.dtype).at[perm].set(k.reshape(pool_shape))
    v_pool = jnp.zeros(pool_shape, v.dtype).at[perm].set(v.reshape(pool_shape))
    got = ops.paged_decode_attention(q, k_pool, v_pool, table, lengths, seq_len=S)
    want = ref.paged_decode_attention_ref(
        q, k_pool, v_pool, table, lengths, seq_len=S
    )
    tol = _attention_tolerance(q, k, v, lengths, want)
    return _compare_attention(
        f"paged_decode_attention B={B} S={S} block_size={block_size}",
        got, want, tol,
    )


def kernel_phase(cfg, seed: int) -> None:
    import jax

    keys = jax.random.split(jax.random.key(seed), 4)
    log("(b) " + check_exit_confidence(cfg, keys[0], 8))
    log("(b) " + check_exit_confidence(cfg, keys[1], 200))
    log("(b) " + check_decode_attention(cfg, keys[2]))
    log("(b) " + check_paged_decode_attention(cfg, keys[3]))


# ---------------------------------------------------------------------------
# (c) serve
# ---------------------------------------------------------------------------


def check_compiled_programs(cfg, params) -> None:
    """The engine's stage-decode programs (dense and paged) and its heads,
    compiled as the engine compiles them, hold the Pallas kernels."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as model_lib
    from repro.serving import steps

    max_len = PROMPT_LEN + GEN_LEN
    n_slots, block_size = 2 * BATCH + 1, 16
    n_blocks = 2 * BATCH * -(-max_len // block_size) + 1
    x = jax.ShapeDtypeStruct((BATCH, 1, cfg.d_model), cfg.dtype)
    rows = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    tables = jax.ShapeDtypeStruct((BATCH, -(-max_len // block_size)), jnp.int32)
    store = jax.eval_shape(
        lambda: model_lib.init_stage_slot_caches(cfg, 1, n_slots, max_len)
    )
    pool, state = jax.eval_shape(
        lambda: model_lib.init_stage_paged_caches(
            cfg, 1, n_slots, n_blocks, block_size, max_len
        )
    )
    programs = {
        "stage-1 decode (dense)": (
            steps.make_stage_decode(cfg, 1), (params, x, store, rows)
        ),
        "stage-1 decode (paged)": (
            steps.make_paged_stage_decode(cfg, 1, max_len),
            (params, x, pool, state, tables, rows),
        ),
        f"exit head {cfg.exit_stages[0]}": (
            steps.make_exit_head_step(cfg, cfg.exit_stages[0]), (params, x)
        ),
        "final head": (steps.make_final_head_step(cfg), (params, x)),
    }
    for name, (fn, args) in programs.items():
        text = fn.lower(*args).compile().as_text()
        check("tpu_custom_call" in text, f"{name}: no Pallas kernel compiled in")
    log(f"(c) compiled programs hold tpu_custom_call: {', '.join(programs)}")


def _check_served(stats, cfg, name: str) -> None:
    import numpy as np

    seqs = stats.sequences_by_rid()
    check(len(seqs) == N_PROMPTS,
          f"{name}: {len(seqs)} of {N_PROMPTS} requests completed")
    for rid, (stage, seq) in seqs.items():
        if stage == cfg.num_stages:
            check(len(seq) == GEN_LEN,
                  f"{name}: request {rid} ran to the final head with "
                  f"{len(seq)} tokens, not {GEN_LEN}")
        else:
            check(1 <= len(seq) <= GEN_LEN,
                  f"{name}: request {rid} exited at stage {stage} with "
                  f"{len(seq)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in seq),
              f"{name}: request {rid} emitted a token outside the vocab")
    conf = np.asarray(stats.confidences)
    check(bool(np.all((conf > 0) & (conf <= 1))),
          f"{name}: a confidence outside (0, 1]")


def serve_phase(cfg, seed: int, clock: CompileClock) -> dict:
    import jax
    import numpy as np

    from repro.launch.serve import build_engine

    t0 = perf_counter()
    engine = build_engine(cfg, seed, num_eds=8)
    jax.block_until_ready(engine.programs.params)
    n_params = sum(int(a.size) for a in jax.tree.leaves(engine.programs.params))
    log(f"(c) engine: {n_params / 1e9:.4f} B params (f32), built in "
        f"{perf_counter() - t0:.1f} s")
    log(f"(c) serving weights: {engine.programs.weight_bytes}")
    engine.configuration_phase()
    log(f"(c) configuration phase: thresholds {engine.thresholds}")
    check_compiled_programs(cfg, engine.programs.weights)

    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=PROMPT_LEN).astype(np.int32)
        for _ in range(N_PROMPTS)
    ]

    def serve(layout: str, name: str):
        # the same seed every serve: identical arrivals, routes and batches
        engine.rng = np.random.default_rng(seed + 2)
        c0, n0 = clock.seconds, clock.programs
        t = perf_counter()
        stats = engine.serve(
            prompts,
            arrival_rate=ARRIVAL_RATE,
            batch_size=BATCH,
            gen_len=GEN_LEN,
            cache_layout=layout,
        )
        wall = perf_counter() - t  # tokens are host ints by now
        _check_served(stats, cfg, name)
        s = stats.summary()
        row = {
            "wall_s": wall,
            "compile_s": clock.seconds - c0,
            "programs_compiled": clock.programs - n0,
            "generated_tokens": s["generated_tokens"],
            "exit_histogram": s["exit_histogram"],
            "num_batches": s["num_batches"],
        }
        log(f"(c) {name}: {row['generated_tokens']} tokens, exits "
            f"{row['exit_histogram']}, {row['num_batches']} batches, wall "
            f"{wall:.2f} s of which compile {row['compile_s']:.2f} s "
            f"({row['programs_compiled']} programs)")
        return stats, row

    report = {"weight_bytes": dict(engine.programs.weight_bytes)}
    runs = {}
    for layout in ("dense", "paged"):
        cold, report[f"{layout} cold"] = serve(layout, f"serve {layout} (cold)")
        warm, report[f"{layout} warm"] = serve(layout, f"serve {layout} (warm)")
        check(warm.sequences_by_rid() == cold.sequences_by_rid(),
              f"{layout}: a warm rerun of the same serve emitted other tokens")
        runs[layout] = cold

    dense, paged = runs["dense"].sequences_by_rid(), runs["paged"].sequences_by_rid()
    same = total = same_exit = 0
    for rid, (stage, seq) in dense.items():
        p_stage, p_seq = paged[rid]
        same_exit += stage == p_stage
        total += max(len(seq), len(p_seq))
        same += sum(a == b for a, b in zip(seq, p_seq))
    report["dense_vs_paged"] = {
        "identical_tokens": same,
        "tokens": total,
        "identical_share": same / total,
        "identical_exit_stage": same_exit,
    }
    log(f"(d) dense vs paged: {same}/{total} tokens identical "
        f"({same / total:.4f}), exit stage identical for {same_exit}/"
        f"{N_PROMPTS} requests")

    # random weights keep every confidence near 1/vocab, far below the
    # configured thresholds: lower them to the upper quartile of the
    # confidences just seen so the branches retire rows mid-generation
    c = float(np.quantile(runs["dense"].confidences, 0.75))
    engine.thresholds[:] = c
    stats, report["paged retire"] = serve("paged", f"serve paged, thresholds {c:.3g}")
    early = sum(n for st, n in stats.summary()["exit_histogram"].items()
                if st < cfg.num_stages)
    check(early > 0, "no early-exit branch retired a row")
    return report


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

    device = device_phase()
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = get_config("stablelm-1.6b")
    log(f"config {cfg.name}: {cfg.num_layers}L d_model {cfg.d_model} "
        f"{cfg.num_heads}H/{cfg.num_kv_heads}KV hd {cfg.head_dim} d_ff "
        f"{cfg.d_ff} vocab {cfg.vocab_size}, {cfg.num_stages} stages, exits "
        f"after {cfg.exit_stages}")

    t = perf_counter()
    kernel_phase(cfg, args.seed)
    log(f"(b) kernels checked in {perf_counter() - t:.1f} s")
    report = serve_phase(cfg, args.seed, clock)

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"(d) peak_bytes_in_use: {peak if peak is not None else 'not reported'}")
    log(f"(d) compile total: {clock.seconds:.1f} s over {clock.programs} programs")
    log("(d) report: " + json.dumps(report, default=str))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        sys.exit(1)
