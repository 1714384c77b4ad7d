"""Distributed seq2seq MT training — the variable-length-gradients workload.

Reference: ``examples/seq2seq/seq2seq.py`` (dagger) (SURVEY.md section 2.8):
LSTM encoder-decoder on WMT/europarl, the workload whose ragged batches
stressed the reference's gradient packer. Under XLA the analogous stress is
the *compile cache*: this example demonstrates the bucketing discipline
(:mod:`chainermn_tpu.datasets.bucketing`) — every batch shape is drawn from
a fixed bucket ladder, so the jitted train step compiles once per bucket.

    python examples/seq2seq/seq2seq.py --communicator naive --iterations 60

Data: synthetic "copy-with-noise translation" pairs (no corpus in this
environment); pass ``--train-file`` (tab-separated token-id lines) for real
data.
"""

from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

import chainermn_tpu
from chainermn_tpu import global_except_hook
from chainermn_tpu.datasets.bucketing import bucket_batches
from chainermn_tpu.models import Seq2Seq, seq2seq_loss
from chainermn_tpu.models.seq2seq import beam_search_decode, greedy_decode
from chainermn_tpu.utils import bleu as bleu_utils

VOCAB = 128
BOS = 1
EOS = 2


def synthetic_pairs(n, seed):
    """tgt = reversed src, EOS-terminated — learnable, ragged."""
    rng = np.random.RandomState(seed)
    pairs = []
    for _ in range(n):
        L = rng.randint(4, 30)
        src = rng.randint(3, VOCAB, size=L)
        tgt = src[::-1].copy()
        pairs.append((list(src), list(tgt) + [EOS]))
    return pairs


def main(argv=None):
    p = argparse.ArgumentParser(description="ChainerMN-TPU example: seq2seq")
    p.add_argument("--communicator", default="naive")
    p.add_argument("--batchsize", type=int, default=32,
                   help="global batch size (must divide by mesh size)")
    p.add_argument("--iterations", type=int, default=60)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--train-file", default=None)
    p.add_argument("--eval", action="store_true",
                   help="after training, greedy-decode a held-out set and "
                        "report corpus BLEU aggregated across ranks "
                        "(the synthetic reversal task needs ~2000+ "
                        "iterations before BLEU leaves zero)")
    p.add_argument("--eval-size", type=int, default=256)
    p.add_argument("--beam", type=int, default=0, metavar="K",
                   help="with --eval: beam-search decode with K beams "
                        "instead of greedy (takes each row's top beam)")
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(args.communicator)
    global_except_hook._add_hook()
    if comm.rank == 0:
        print(f"communicator: {comm}")

    if args.train_file:
        pairs = []
        with open(args.train_file) as f:
            for line in f:
                s, t = line.rstrip("\n").split("\t")
                pairs.append(
                    ([int(w) for w in s.split()], [int(w) for w in t.split()])
                )
    else:
        pairs = synthetic_pairs(4096, seed=0)
    pairs = chainermn_tpu.scatter_dataset(pairs, comm, shuffle=True, seed=7)
    # Re-gather the global batch per step (synchronized iterator semantics):
    # each process batches its own shard; the mesh shards the batch dim.

    model = Seq2Seq(src_vocab=VOCAB, tgt_vocab=VOCAB, embed=64, hidden=128)
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(args.lr), comm
    )

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axes = comm.grad_axes

    def build_step():
        def local_step(params, opt_state, batch):
            src, tgt, sm, tm = batch
            tgt_in = jnp.concatenate(
                [jnp.full((tgt.shape[0], 1), BOS, tgt.dtype), tgt[:, :-1]],
                axis=1,
            )

            def loss_fn(p):
                logits = model.apply(p, src, tgt_in, sm, tm)
                return seq2seq_loss(logits, tgt, tm)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            grads = jax.lax.pmean(grads, axes)
            loss = jax.lax.pmean(loss, axes)
            updates, opt_state = optimizer.actual_optimizer.update(
                grads, opt_state, params
            )
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return jax.jit(
            shard_map(
                local_step,
                mesh=comm.mesh,
                in_specs=(P(), P(), P(axes)),
                out_specs=(P(), P(), P()),
                check_vma=False,
            )
        )

    step = build_step()

    params = None
    opt_state = None
    it = 0
    compiled_buckets = set()
    while it < args.iterations:
        for batch in bucket_batches(pairs, args.batchsize, drop_remainder=True):
            if it >= args.iterations:
                break
            src = jnp.asarray(batch["src"])
            tgt = jnp.asarray(batch["tgt"])
            sm = jnp.asarray(batch["src_mask"])
            tm = jnp.asarray(batch["tgt_mask"])
            if params is None:
                tgt_in = jnp.concatenate(
                    [jnp.full((tgt.shape[0], 1), BOS, tgt.dtype),
                     tgt[:, :-1]], axis=1,
                )
                params = model.init(jax.random.key(0), src, tgt_in, sm, tm)
                params = comm.bcast_data(params)
                opt_state = optimizer.actual_optimizer.init(params)
            if batch["bucket"] not in compiled_buckets and comm.rank == 0:
                compiled_buckets.add(batch["bucket"])
                print(f"  compiling bucket length {batch['bucket']}")
            params, opt_state, loss = step(params, opt_state, (src, tgt, sm, tm))
            it += 1
            if comm.rank == 0 and it % 20 == 0:
                print(f"iter {it}/{args.iterations} loss={float(loss):.4f}")
    if comm.rank == 0:
        print(f"final loss={float(loss):.4f} "
              f"({len(compiled_buckets)} bucket compilations)")

    result = {"loss": float(loss)}
    if args.eval:
        # Held-out set, sharded across ranks; greedy decode under jit per
        # source-length bucket; corpus BLEU from allreduce-summed n-gram
        # statistics (reference: the seq2seq example's BLEU eval, SURVEY.md
        # §2.8 — aggregation via the multi-node evaluator).
        held_out = synthetic_pairs(args.eval_size, seed=1234)
        shard = chainermn_tpu.scatter_dataset(held_out, comm, shuffle=False)
        if args.beam:
            decode = jax.jit(
                lambda s, m: beam_search_decode(
                    model, params, s, m, 36, args.beam, bos=BOS, eos=EOS
                )[0][:, 0]  # each row's best hypothesis
            )
        else:
            decode = jax.jit(
                lambda s, m: greedy_decode(
                    model, params, s, m, max_len=36, bos=BOS, eos=EOS
                )
            )

        def local_bleu_stats() -> dict:
            stats = []
            for batch in bucket_batches(
                shard, args.batchsize, drop_remainder=False
            ):
                hyp = np.asarray(
                    decode(jnp.asarray(batch["src"]),
                           jnp.asarray(batch["src_mask"]))
                )
                for row, ref in list(
                    zip(hyp, batch["tgt_raw"])
                )[: batch["n_real"]]:
                    stats.append(bleu_utils.bleu_stats(
                        bleu_utils.truncate_at_eos(row, EOS),
                        bleu_utils.truncate_at_eos(ref, EOS),
                    ))
            return bleu_utils.sum_stats(stats)

        evaluate = chainermn_tpu.create_multi_node_evaluator(
            local_bleu_stats, comm, reduce="sum",
            finalize=lambda total: {
                "bleu": bleu_utils.bleu_from_stats(total)
            },
        )
        result["bleu"] = evaluate()["bleu"]
        if comm.rank == 0:
            print(f"eval: corpus BLEU = {result['bleu']:.4f} "
                  f"({args.eval_size} held-out pairs, all ranks)")
    return result


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
