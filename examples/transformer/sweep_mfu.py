"""Transformer-LM MFU sweep — perf methodology tool for the tracked
``transformer_mfu`` metric (SURVEY.md section 6 / docs/benchmarks.md).

Times the full train step (fwd + bwd + grad allreduce + adam) across a
small grid of the knobs that actually move single-chip MFU — remat
policy, fused-LM-head chunk count, flash block sizes, and head count at
fixed d_model (H16×D64 vs H8×D128: identical params and model FLOPs,
but head dim is the MXU contraction depth and the flash kernel's VMEM
lane width — D=64 fills half of each) — and prints one JSON line per
variant plus a ranked summary. Run on the real chip:

    python examples/transformer/sweep_mfu.py
    python examples/transformer/sweep_mfu.py --layers 8 --d-model 1024 \
        --seq-len 2048 --batch 16 --steps 8

The defaults mirror ``bench.py``'s accel transformer config so the best
variant's settings can be transplanted straight into the benchmark.
MFU convention: MODEL flops (6·P per token + 6·L·T·d attention), not
hardware flops — remat recompute is the price paid, not useful work.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

# Canonical peak-FLOPs table and true-completion sync — shared with the
# tracked benchmark so sweep MFU is directly comparable to bench.py's
# transformer_mfu (a diverging copy once reported half the true v5e MFU).
from bench import _fetch_scalar, _peak_flops

from chainermn_tpu import create_communicator, create_multi_node_optimizer
from chainermn_tpu.models import TransformerLM, lm_loss_fused
from chainermn_tpu.ops.flash_attention import flash_attention, interpret_on


def time_variant(comm, args, *, remat: str, n_chunks: int,
                 block_q: int, block_k: int, batch: int,
                 n_heads: int, db: bool = True) -> dict:
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    interpret = interpret_on(jax.devices()[0].platform)

    def attn(q, k, v, *, causal, scale):
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)

    # n_heads at fixed d_model: identical params/model-FLOPs, but
    # D = d_model/heads is the MXU contraction depth and the VMEM lane
    # width in the flash kernel — D=64 fills half of each.
    model = TransformerLM(
        num_layers=args.layers, d_model=args.d_model,
        num_heads=n_heads, d_ff=args.d_ff, max_len=args.seq_len,
        remat=remat != "none",
        remat_policy="dots" if remat != "nothing" else "nothing",
        return_hidden=True, attention_fn=attn,
    )
    B, T, steps = batch * comm.size, args.seq_len, args.steps
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (B, T), 0, model.vocab_size
    )
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        tokens = multihost_utils.host_local_array_to_global_array(
            tokens, comm.mesh, P()
        )
    params = jax.jit(lambda k, t: model.init(k, t, train=True))(
        jax.random.PRNGKey(1), tokens[:2]
    )
    opt = create_multi_node_optimizer(
        optax.adam(1e-4), comm, double_buffering=db,
        allreduce_grad_dtype=jnp.bfloat16,
    )

    def loss_fn(p, tok):
        hidden = model.apply(p, tok, train=True)
        emb = p["params"]["tok_emb"]["embedding"]
        return lm_loss_fused(hidden, emb, tok, n_chunks=n_chunks)

    def local(params, opt_state, tok):
        def one(carry, _):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, tok)
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            one, (params, opt_state), None, length=steps
        )
        return losses[-1]

    fn = jax.jit(shard_map(
        local, mesh=comm.mesh,
        in_specs=(P(), P(), P(comm.grad_axes)), out_specs=P(),
        check_vma=False,
    ))
    opt_state = opt.init(params)
    t_c0 = time.perf_counter()
    _fetch_scalar(fn(params, opt_state, tokens))  # compile + warm
    compile_s = time.perf_counter() - t_c0
    t0 = time.perf_counter()
    _fetch_scalar(fn(params, opt_state, tokens))
    dt = (time.perf_counter() - t0) / steps

    n_params = sum(x.size for x in jax.tree.leaves(params))
    model_flops = (
        (6 * n_params + 6 * args.layers * T * args.d_model) * B * T
        / comm.size
    )
    out = {
        "remat": remat, "n_chunks": n_chunks, "batch": batch,
        "block_q": block_q, "block_k": block_k, "heads": n_heads,
        "db": db,
        "step_ms": round(dt * 1e3, 2),
        "tokens_per_sec": round(B * T / dt, 1),
        "compile_s": round(compile_s, 1),
    }
    peak = _peak_flops(jax.devices()[0].device_kind)
    if peak:
        out["mfu"] = round(model_flops / dt / peak, 4)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--communicator", default="xla")
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--d-model", type=int, default=1024)
    p.add_argument("--db", type=str, default="true",
                   help="comma list of true/false: double-buffered "
                        "allreduce (baseline-identity default true; on "
                        "one chip the bank carry is pure cost)")
    p.add_argument("--heads", type=str, default="16,8",
                   help="comma list of head counts at fixed d_model "
                        "(same params/FLOPs; head dim = d_model/heads "
                        "sets MXU contraction depth)")
    p.add_argument("--d-ff", type=int, default=4096)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--batch", type=str, default="16",
                   help="comma list of per-device batch sizes")
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--remat", type=str, default="dots,none,nothing",
                   help="comma list of none|dots|nothing (granularity)")
    p.add_argument("--chunks", type=str, default="8,16,32")
    p.add_argument("--blocks", type=str, default="512x1024,256x512",
                   help="comma list of block_q x block_k")
    args = p.parse_args(argv)

    comm = create_communicator(args.communicator)
    remats = []
    for v in args.remat.split(","):
        v = v.strip().lower()
        # legacy spellings from earlier rounds keep working
        v = {"true": "dots", "false": "none"}.get(v, v)
        if v not in ("none", "dots", "nothing"):
            p.error(f"--remat values must be none|dots|nothing, got {v!r}")
        remats.append(v)
    chunks = [int(v) for v in args.chunks.split(",")]
    blocks = [tuple(int(v) for v in b.split("x"))
              for b in args.blocks.split(",")]
    batches = [int(v) for v in args.batch.split(",")]
    head_counts = [int(v) for v in str(args.heads).split(",")]
    for h in head_counts:
        if h < 1 or args.d_model % h:
            p.error(f"--heads values must divide d_model, got {h}")
    dbs = []
    for v in args.db.split(","):
        v = v.strip().lower()
        if v not in ("true", "false"):
            p.error(f"--db values must be true/false, got {v!r}")
        dbs.append(v == "true")

    results = []
    for remat, n_chunks, (bq, bk), batch, heads, db in itertools.product(
        remats, chunks, blocks, batches, head_counts, dbs
    ):
        try:
            r = time_variant(comm, args, remat=remat, n_chunks=n_chunks,
                             block_q=bq, block_k=bk, batch=batch,
                             n_heads=heads, db=db)
        except Exception as e:  # OOM / Mosaic layout reject: keep sweeping
            r = {"remat": remat, "n_chunks": n_chunks, "block_q": bq,
                 "block_k": bk, "batch": batch, "heads": heads, "db": db,
                 "error": f"{type(e).__name__}: {e}"[:160]}
        print(json.dumps(r), flush=True)
        results.append(r)

    ok = [r for r in results if "step_ms" in r]
    # Best by MFU (fallback throughput): batch is a grid dimension, so
    # step_ms ordering would rank the smallest batch first regardless of
    # efficiency. The fallback is PER-RUN, not per-row — mixing mfu
    # (<=1) with raw throughput (thousands) would rank any mfu-less row
    # first; a row missing mfu in an mfu-bearing run ranks last (0).
    if any("mfu" in r for r in ok):
        ok.sort(key=lambda r: -r.get("mfu", 0))
    else:
        ok.sort(key=lambda r: -r.get("tokens_per_sec", 0))
    if ok:
        print(json.dumps({"best": ok[0], "n_variants": len(results)}))
    return ok


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
