"""Transformer-base LM training — the double-buffered-allreduce workload.

BASELINE.json config: "Transformer-base LM (new — large embedding grads,
double-buffered allreduce)". Demonstrates the v1.3-era optimizer features
(``double_buffering=True``, ``allreduce_grad_dtype='bfloat16'`` — SURVEY.md
section 2.3) on a modern workload, plus optional ring-attention sequence
parallelism for long context (``--sequence-parallel``).

    python examples/transformer/train_transformer_lm.py \
        --communicator naive --iterations 40 --double-buffering
    python examples/transformer/train_transformer_lm.py \
        --communicator naive --sequence-parallel --seq-len 512
    python examples/transformer/train_transformer_lm.py \
        --communicator naive --packed --num-kv-heads 2
    python examples/transformer/train_transformer_lm.py \
        --communicator xla --model olmoe-1b-7b --layers 1 \
        --batchsize 4 --seq-len 4096      # one v5e chip, published widths
    python examples/transformer/train_transformer_lm.py \
        --communicator xla --model ouro-2.6b --layers 2 \
        --batchsize 1 --seq-len 4096      # looped: 2 blocks, 4 passes
    python examples/transformer/train_transformer_lm.py \
        --communicator xla --model lfm2-8b-a1b --layers 3 \
        --batchsize 1 --seq-len 4096      # conv, conv, attention; 32 experts
"""

from __future__ import annotations

import argparse
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

import chainermn_tpu
from chainermn_tpu import global_except_hook
from chainermn_tpu.models import (
    MODEL_CONFIGS,
    ROUTER_STATE,
    TransformerLM,
    diffusion_noise_key,
    diffusion_noise_state,
    head_table,
    lm_from_config,
    lm_loss,
    lm_loss_block_diffusion,
    lm_loss_fused,
    lm_loss_looped,
    lm_loss_moe,
)
from chainermn_tpu.training import make_train_step
from chainermn_tpu.training.train_step import create_train_state

VOCAB = 1024


def synthetic_tokens(rng, batch, seqlen):
    """Markov-ish synthetic text: next token correlates with current."""
    x = np.zeros((batch, seqlen), np.int32)
    x[:, 0] = rng.integers(0, VOCAB, size=batch)
    drift = rng.integers(1, 17, size=batch)
    for t in range(1, seqlen):
        stay = rng.random(batch) < 0.8
        x[:, t] = np.where(stay, (x[:, t - 1] + drift) % VOCAB,
                           rng.integers(0, VOCAB, size=batch))
    return x


def _interpret(comm) -> bool:
    from chainermn_tpu.ops.flash_attention import interpret_on

    return interpret_on(comm.mesh.devices.flat[0].platform)


def _make_optimizer(args, comm):
    """One builder for every training path in this example: local SGD
    (frequency lever) or the per-step multi-node wrapper (width/overlap
    levers) — mutually exclusive, validated at parse time."""
    if args.local_sgd:
        return chainermn_tpu.create_local_sgd(
            optax.adamw(args.lr), comm, sync_every=args.local_sgd,
        )
    return chainermn_tpu.create_multi_node_optimizer(
        optax.adamw(args.lr), comm,
        double_buffering=args.double_buffering,
        error_feedback=args.error_feedback,
    )


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: Transformer LM"
    )
    p.add_argument("--communicator", default="naive")
    p.add_argument("--batchsize", type=int, default=8,
                   help="per-mesh-slot batch size (data-parallel mode)")
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--iterations", type=int, default=40)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--double-buffering", action="store_true")
    p.add_argument("--allreduce-grad-dtype", default="bfloat16")
    p.add_argument("--mlm", action="store_true",
                   help="masked-LM pretraining on the BIDIRECTIONAL "
                        "encoder form (causal=False): BERT-style 80/10/10 "
                        "corruption, loss on masked positions only; "
                        "excludes --generate/--beam (no autoregressive "
                        "decode on an encoder)")
    p.add_argument("--local-sgd", type=int, default=0, metavar="H",
                   help="periodic parameter averaging every H steps "
                        "instead of the per-step gradient allreduce; "
                        "0 = off")
    p.add_argument("--error-feedback", action="store_true",
                   help="EF-SGD for the int8 quantized wire (requires "
                        "--allreduce-grad-dtype int8); shard-level on "
                        "the two_dimensional communicator")
    p.add_argument("--sequence-parallel", action="store_true",
                   help="shard the sequence over the mesh (ring attention)")
    p.add_argument("--packed", action="store_true",
                   help="pack variable-length documents into each row with "
                        "segment-id flash-attention masks (cross-document "
                        "attention and loss are masked)")
    p.add_argument("--num-kv-heads", type=int, default=None,
                   help="GQA: fewer kv heads than q heads (must divide)")
    p.add_argument("--pos-encoding", default="learned",
                   choices=("learned", "rope"),
                   help="absolute learned table (reference-style) or "
                        "rotary (no position parameters)")
    p.add_argument("--num-layers", type=int, default=6)
    p.add_argument("--model", default=None, choices=sorted(MODEL_CONFIGS),
                   help="train a published architecture at its own widths "
                        "through the model description (models."
                        "lm_from_config): flash attention, fused head, and "
                        "for an expert model dropless routing with its "
                        "auxiliary losses; --layers cuts the depth")
    p.add_argument("--layers", type=int, default=None, metavar="N",
                   help="with --model: layers to build (default: all)")
    p.add_argument("--d-model", type=int, default=512)
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, greedy-decode N tokens from a "
                        "synthetic prompt with the KV cache (data-parallel "
                        "mode only)")
    p.add_argument("--window", type=int, default=0, metavar="W",
                   help="causal sliding-window attention of width W via the "
                        "flash kernel (0 = full causal; composes with "
                        "--packed and --sequence-parallel)")
    p.add_argument("--beam", type=int, default=0, metavar="K",
                   help="with --generate: beam-search decode with K beams "
                        "instead of greedy")
    args = p.parse_args(argv)
    # Fail flag conflicts BEFORE any expensive setup (compile, data).
    # (--allreduce-grad-dtype configures the COMMUNICATOR's wire and
    # defaults to bf16 here; under local SGD that wire simply never
    # fires, so only the explicit optimizer opt-ins conflict.)
    if args.local_sgd and (args.double_buffering or args.error_feedback):
        p.error("--local-sgd replaces the per-step gradient wire; "
                "--double-buffering/--error-feedback would be "
                "silently ignored")
    if args.mlm and (args.generate or args.beam):
        p.error("--mlm is an encoder: no autoregressive decode "
                "(--generate/--beam)")
    if args.model and (args.mlm or args.sequence_parallel or args.packed
                       or args.window or args.generate):
        p.error("--model trains the published block data-parallel; it "
                "composes with the optimizer flags only")
    if args.layers is not None and not args.model:
        p.error("--layers cuts the depth of a --model")
    if args.mlm and (args.window or args.sequence_parallel or args.packed):
        p.error("--mlm composes with the plain data-parallel path only "
                "(windows/SP/packing are causal-LM features here)")
    if args.local_sgd and args.sequence_parallel:
        p.error("--local-sgd is not wired into the sequence-parallel "
                "path (it builds its own per-step pmean loop); drop one "
                "of the flags")

    comm = chainermn_tpu.create_communicator(
        args.communicator,
        allreduce_grad_dtype=args.allreduce_grad_dtype or None,
    )
    global_except_hook._add_hook()
    if comm.rank == 0:
        print(f"communicator: {comm}  sp={args.sequence_parallel}")

    # bf16 with compiled kernels on the chip; f32 on the CPU test mesh,
    # where Pallas kernels are interpreted. interpret_on raises for any
    # other accelerator rather than training it through the interpreter.
    compute_dtype = (
        jnp.float32 if _interpret(comm) else jnp.bfloat16
    )
    rng = np.random.default_rng(0)

    if args.sequence_parallel and args.packed:
        raise SystemExit(
            "--sequence-parallel with --packed is not wired in this "
            "example (ring attention does accept segment_ids — see "
            "ring_attention_local — but this CLI keeps the modes separate)"
        )
    if args.model:
        run_named_model(args, comm, compute_dtype, rng)
    elif args.sequence_parallel:
        run_sequence_parallel(args, comm, compute_dtype, rng)
    elif args.packed:
        run_packed(args, comm, compute_dtype, rng)
    else:
        run_data_parallel(args, comm, compute_dtype, rng)


def pack_documents(rng, batch, seqlen):
    """Pack 2-5 variable-length synthetic documents per row: returns
    ``(tokens, segment_ids)`` — the normal LM data layout (SURVEY.md §5
    long-context gap; the reference's seq2seq bucketing was the 2017
    answer to the same problem)."""
    if seqlen < 32:
        raise SystemExit(
            f"--packed needs --seq-len >= 32 (got {seqlen}): rows hold up "
            "to 5 documents with 8-token margins"
        )
    tokens = np.zeros((batch, seqlen), np.int32)
    seg = np.zeros((batch, seqlen), np.int32)
    for b in range(batch):
        n_docs = rng.integers(2, 6)
        cuts = np.sort(rng.choice(np.arange(8, seqlen - 8), n_docs - 1,
                                  replace=False))
        bounds = [0, *cuts.tolist(), seqlen]
        for d in range(n_docs):
            lo, hi = bounds[d], bounds[d + 1]
            tokens[b:b + 1, lo:hi] = synthetic_tokens(rng, 1, hi - lo)
            seg[b, lo:hi] = d
    return tokens, seg


def run_packed(args, comm, compute_dtype, rng):
    """Packed-sequence training: flash attention with segment-id masks so
    documents never attend across their boundaries, and the next-token loss
    skips cross-document targets."""
    from chainermn_tpu.ops.flash_attention import flash_attention

    interpret = _interpret(comm)

    def attn(q, k, v, *, causal, scale, segment_ids=None):
        # window composes with the packed-segment masks in the kernel
        # (0 = no window — full causal within each document).
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               segment_ids=segment_ids,
                               window=args.window or None,
                               interpret=interpret)

    model = TransformerLM(
        vocab_size=VOCAB, num_layers=args.num_layers,
        d_model=args.d_model, d_ff=4 * args.d_model,
        max_len=args.seq_len, compute_dtype=compute_dtype,
        attention_fn=attn, num_kv_heads=args.num_kv_heads,
        pos_encoding=args.pos_encoding,
        window=args.window or None,
    )
    global_batch = args.batchsize * comm.size
    tokens0, seg0 = pack_documents(rng, global_batch, args.seq_len)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.asarray(tokens0[:1])
    )["params"]

    def loss_fn(params, batch):
        tokens, seg = batch
        logits = model.apply({"params": params}, tokens, segment_ids=seg)
        # Mask targets that would cross a document boundary.
        valid = jnp.concatenate(
            [jnp.ones_like(seg[:, :1]), (seg[:, 1:] == seg[:, :-1])], axis=1
        )
        return lm_loss(logits, tokens, mask=valid)

    optimizer = _make_optimizer(args, comm)
    state = create_train_state(params, optimizer, comm)
    step = make_train_step(loss_fn, optimizer, comm)

    t0 = time.perf_counter()
    for it in range(args.iterations):
        tokens, seg = pack_documents(rng, global_batch, args.seq_len)
        state, metrics = step(state, (jnp.asarray(tokens), jnp.asarray(seg)))
        if comm.rank == 0 and (it + 1) % 10 == 0:
            jax.block_until_ready(metrics["loss"])
            tps = global_batch * args.seq_len * (it + 1) / (
                time.perf_counter() - t0
            )
            print(
                f"iter {it + 1}/{args.iterations} "
                f"loss={float(metrics['loss']):.4f} ({tps:,.0f} tok/s, packed)"
            )
    jax.block_until_ready(state.params)
    if comm.rank == 0:
        print("done (packed)")


def run_named_model(args, comm, compute_dtype, rng):
    """A published architecture from its ``config.json`` (``--model``),
    through the same front door as everything else here."""
    from chainermn_tpu.ops.flash_attention import flash_attention

    def attention_fn(q, k, v, *, causal, scale):
        return flash_attention(q, k, v, causal=causal, scale=scale)

    model = lm_from_config(
        MODEL_CONFIGS[args.model], num_layers=args.layers,
        compute_dtype=compute_dtype, attention_fn=attention_fn,
        return_hidden=True,
    )
    if args.seq_len > model.max_len:
        raise SystemExit(f"{args.model} has {model.max_len} positions")
    global_batch = args.batchsize * comm.size
    tokens0 = synthetic_tokens(rng, global_batch, args.seq_len)
    block_diffusion = bool(model.arch.diffusion_block)
    if block_diffusion:
        # its training pass reads a clean and a noised copy of every row
        tokens0 = np.tile(tokens0, (1, 2))
    variables = jax.jit(model.init)(
        jax.random.key(0), jnp.asarray(tokens0[:1]))
    params = variables["params"]
    # a router's selection bias (LFM2's) is state, not a parameter: it
    # rides the train state as model_state, here at its initial zero
    router_state = variables.get(ROUTER_STATE, ())

    if block_diffusion:
        # SDAR's: the step draws its noise itself, from a key the train
        # state counts up as model_state; the synthetic ids lie below
        # VOCAB, and the mask token is the vocabulary's last
        if model.vocab_size <= VOCAB:
            raise SystemExit(f"{args.model}: the mask token needs an id "
                             f"above the data's {VOCAB}")
        router_state = diffusion_noise_state(0)

        def loss_fn(params, tokens, noise_state):
            key, noise_state = diffusion_noise_key(noise_state)
            loss, metrics = lm_loss_block_diffusion(
                model, params, tokens, key, mask_id=model.vocab_size - 1)
            return loss, (metrics, noise_state)
    elif model.expert_layers:
        # a sigmoid router has no auxiliary losses; DeepSeek-V2's is the
        # balance loss a sequence at a time, alone
        coefs = {} if model.arch.router_score == "softmax" else dict(
            load_balance_coef=0.0, z_loss_coef=0.0)
        if model.arch.seq_aux:
            coefs = dict(load_balance_coef=0.0, z_loss_coef=0.0,
                         seq_aux_coef=0.001)

        def loss_fn(params, tokens, router_state=()):
            loss, metrics = lm_loss_moe(
                model, params, tokens, router_state=router_state or None,
                **coefs)
            return loss, (metrics, router_state)
    elif model.looped:

        def loss_fn(params, tokens):
            return lm_loss_looped(model, params, tokens)
    else:

        def loss_fn(params, tokens):
            hidden = model.apply({"params": params}, tokens)
            return lm_loss_fused(hidden, head_table(params, model.arch),
                                 tokens, compute_dtype=compute_dtype)

    optimizer = _make_optimizer(args, comm)
    state = create_train_state(params, optimizer, comm,
                               model_state=router_state)
    step = make_train_step(loss_fn, optimizer, comm)
    t0 = time.perf_counter()
    for it in range(args.iterations):
        batch = jnp.asarray(
            synthetic_tokens(rng, global_batch, args.seq_len))
        state, metrics = step(state, batch)
        if comm.rank == 0 and (it + 1) % 10 == 0:
            jax.block_until_ready(metrics["loss"])
            tps = global_batch * args.seq_len * (it + 1) / (
                time.perf_counter() - t0
            )
            extra = "".join(
                f" {k.split('/')[1]}={float(v):.3f}"
                for k, v in sorted(metrics.items())
                if k.startswith(("moe/", "loop/", "bd/"))
                and jnp.ndim(v) == 0)
            print(
                f"iter {it + 1}/{args.iterations} "
                f"loss={float(metrics['loss']):.4f}{extra} "
                f"({tps:,.0f} tok/s, {args.model})"
            )
    jax.block_until_ready(state.params)
    if comm.rank == 0:
        print(f"done ({args.model}, {model.num_layers} layers)")


def run_data_parallel(args, comm, compute_dtype, rng):
    attention_fn = None
    if args.window:
        # Local attention needs the flash kernel (the blockwise default
        # has no window support); out-of-band blocks skip their matmuls.
        # The model also carries `window` so KV-cache decoding
        # (--generate) masks the same band — train and inference agree.
        from chainermn_tpu.ops.flash_attention import flash_attention

        def attention_fn(q, k, v, *, causal, scale):
            return flash_attention(q, k, v, causal=causal, scale=scale,
                                   window=args.window)

    model = TransformerLM(
        vocab_size=VOCAB, num_layers=args.num_layers,
        d_model=args.d_model, d_ff=4 * args.d_model,
        max_len=args.seq_len, compute_dtype=compute_dtype,
        num_kv_heads=args.num_kv_heads,
        pos_encoding=args.pos_encoding,
        attention_fn=attention_fn,
        window=args.window or None,
        causal=not args.mlm,
    )
    global_batch = args.batchsize * comm.size
    tokens0 = synthetic_tokens(rng, global_batch, args.seq_len)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.asarray(tokens0[:1])
    )["params"]

    if args.mlm:
        from chainermn_tpu.models import mlm_corrupt, mlm_loss

        MASK_ID = VOCAB - 1  # reserve the top id as [MASK]
        corrupt = jax.jit(functools.partial(
            mlm_corrupt, mask_id=MASK_ID, vocab_size=VOCAB, rate=0.15,
        ))

        def loss_fn(params, batch):
            x, targets, sel = batch
            logits = model.apply({"params": params}, x)
            return mlm_loss(logits, targets, sel)

        def make_batch(it):
            # Data lives in [0, MASK_ID): real tokens must never equal
            # the reserved [MASK] symbol or the 80/10/10 recipe muddies.
            targets = jnp.asarray(
                synthetic_tokens(rng, global_batch, args.seq_len)
            ) % MASK_ID
            x, sel = corrupt(jax.random.PRNGKey(it), targets)
            return (x, targets, sel)
    else:

        def loss_fn(params, tokens):
            logits = model.apply({"params": params}, tokens)
            return lm_loss(logits, tokens)

        def make_batch(it):
            return jnp.asarray(
                synthetic_tokens(rng, global_batch, args.seq_len)
            )

    optimizer = _make_optimizer(args, comm)
    state = create_train_state(params, optimizer, comm)
    step = make_train_step(loss_fn, optimizer, comm)

    t0 = time.perf_counter()
    for it in range(args.iterations):
        state, metrics = step(state, make_batch(it))
        if comm.rank == 0 and (it + 1) % 10 == 0:
            jax.block_until_ready(metrics["loss"])
            tps = global_batch * args.seq_len * (it + 1) / (
                time.perf_counter() - t0
            )
            print(
                f"iter {it + 1}/{args.iterations} "
                f"loss={float(metrics['loss']):.4f} ({tps:,.0f} tok/s)"
            )
    jax.block_until_ready(state.params)
    if args.generate and comm.rank == 0:
        # Inference demo on the just-trained weights: KV-cache greedy
        # decode (one jitted scan of single-token steps — see
        # chainermn_tpu.models.transformer.generate).
        from chainermn_tpu.models import generate

        prompt = jnp.asarray(
            synthetic_tokens(rng, 2, min(8, args.seq_len))
        )
        n = min(args.seq_len, prompt.shape[1] + args.generate)
        if args.beam:
            from chainermn_tpu.models import beam_search

            beams, bscores = beam_search(
                model, {"params": state.params}, prompt, n, args.beam,
                pad_id=-1,
            )
            print(f"beam_search (K={args.beam}): best scores "
                  f"{np.asarray(bscores[:, 0]).round(2).tolist()}; top "
                  f"continuations "
                  f"{np.asarray(beams[:, 0, prompt.shape[1]:]).tolist()}")
        out = generate(
            model, {"params": state.params}, prompt, n,
            pad_id=-1,  # synthetic tokens include 0; nothing is padding
        )
        print(f"generate: prompt {prompt.shape} -> {out.shape}; "
              f"continuations {np.asarray(out[:, prompt.shape[1]:]).tolist()}")
    if comm.rank == 0:
        print("done (mlm)" if args.mlm else "done (data-parallel)")


def run_sequence_parallel(args, comm, compute_dtype, rng):
    """Long-context mode: ONE sequence sharded over the whole mesh, ring
    attention streaming K/V blocks over ICI."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.parallel.ring_attention import ring_attention_local

    ax = comm.axis_name
    n = comm.size
    if args.seq_len % n:
        raise SystemExit(f"--seq-len must be divisible by mesh size {n}")
    t_local = args.seq_len // n

    if args.window:
        # Local attention: neighbour-tail exchanges instead of the full
        # K/V ring — O(window) communication per layer, any width.
        from chainermn_tpu.parallel.local_attention import (
            sliding_window_attention_local,
        )

        def ring_attn(q, k, v, *, causal, scale):
            return sliding_window_attention_local(
                q, k, v, ax, window=args.window, scale=scale
            )
    else:

        def ring_attn(q, k, v, *, causal, scale):
            return ring_attention_local(q, k, v, ax, causal=causal,
                                        scale=scale)

    model = TransformerLM(
        vocab_size=VOCAB, num_layers=args.num_layers,
        d_model=args.d_model, d_ff=4 * args.d_model,
        max_len=args.seq_len, compute_dtype=compute_dtype,
        attention_fn=ring_attn, num_kv_heads=args.num_kv_heads,
        pos_encoding=args.pos_encoding,
    )
    ref = TransformerLM(
        vocab_size=VOCAB, num_layers=args.num_layers,
        d_model=args.d_model, d_ff=4 * args.d_model,
        max_len=args.seq_len, compute_dtype=compute_dtype,
        num_kv_heads=args.num_kv_heads,
        pos_encoding=args.pos_encoding,
    )
    batch = 2
    tokens0 = synthetic_tokens(rng, batch, args.seq_len)
    params = jax.jit(ref.init)(jax.random.key(0), jnp.asarray(tokens0[:1]))
    opt = optax.adamw(args.lr)
    opt_state = opt.init(params)

    def local_step(params, opt_state, tokens):
        idx = jax.lax.axis_index(ax)

        def loss_fn(p):
            # The shard's GLOBAL positions serve both encodings: a learned
            # table gathers its rows (no more whole-table rolling + params
            # surgery), rotary rotates by them directly.
            pos = idx * t_local + jnp.arange(t_local, dtype=jnp.int32)
            logits = model.apply(p, tokens, positions=pos)
            return lm_loss(logits, tokens)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.lax.pmean(grads, ax)
        loss = jax.lax.pmean(loss, ax)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(
        shard_map(
            local_step,
            mesh=comm.mesh,
            in_specs=(P(), P(), P(None, ax)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )

    t0 = time.perf_counter()
    for it in range(args.iterations):
        tokens = synthetic_tokens(rng, batch, args.seq_len)
        params, opt_state, loss = step(params, opt_state, jnp.asarray(tokens))
        if comm.rank == 0 and (it + 1) % 10 == 0:
            jax.block_until_ready(loss)
            tps = batch * args.seq_len * (it + 1) / (time.perf_counter() - t0)
            print(
                f"iter {it + 1}/{args.iterations} loss={float(loss):.4f} "
                f"({tps:,.0f} tok/s, seq {args.seq_len} over {n} shards)"
            )
    jax.block_until_ready(params)
    if comm.rank == 0:
        print("done (sequence-parallel)")


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
