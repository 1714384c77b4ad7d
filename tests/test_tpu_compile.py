"""What only the TPU's compiler can say, compiled here for a v5e 2x2 that
is described and not attached (no chip, no time): the option
``make_train_step`` gives its jit on several TPU devices is one this
libtpu knows, and under it the default gradient reduction's all_to_alls
come out as asynchronous pairs. A libtpu that renames or drops the
option fails here, and not in a user's step. And Mosaic takes the gated
short convolution's two kernels at the LFM2 cell's shape, which the
interpreter's tests cannot say (it accepts layouts Mosaic refuses); so
it does the grouped matmul's and the flash kernels' under the masks block
diffusion calls them with and at latent attention's keys of 192 on values
of 128, and a small SDAR step holds nine Mosaic calls
a layer under ``bd_attention`` and no lane reduction of the in-block
part's old spelling; and a small step on a share of the experts holds its
expert section once, in a loop's body, with the Mosaic calls the
straight-line spelling has and no ``conditional``.

One file, one fixture: only one process may load the TPU's library, so
the topology is described inside the fixture and nowhere at import."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chainermn_tpu import create_communicator, create_multi_node_optimizer
from chainermn_tpu.ops import short_conv
from chainermn_tpu.parallel import collectives
from chainermn_tpu.training import make_train_step
from chainermn_tpu.training.train_step import TrainState


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _loss(params, batch):
    return jnp.mean((jnp.tanh(batch @ params["w"]) + params["b"]) ** 2)


def _compiled_step(devices):
    """The default optimizer's step over ``devices``, compiled from
    shapes: a 1024 x 512 matrix (1 MiB on the bf16 wire) and a vector."""
    comm = create_communicator("xla", devices=devices,
                               allreduce_grad_dtype=jnp.bfloat16)
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    step = make_train_step(_loss, opt, comm)
    params = {"w": jnp.zeros((1024, 512)), "b": jnp.zeros((512,))}
    state = jax.eval_shape(lambda: TrainState(
        params, opt.init(params), jnp.zeros((), jnp.int32)))
    replicated = NamedSharding(comm.mesh, P())
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=replicated), state)
    batch = jax.ShapeDtypeStruct(
        (8 * len(devices), 1024), jnp.float32,
        sharding=NamedSharding(comm.mesh, P(comm.grad_axes)))
    return step.lower(state, batch).compile().as_text()


def test_several_tpu_devices_get_the_option_and_one_device_none(topo):
    devices = list(topo.devices)
    assert len(devices) == 4 and devices[0].platform == "tpu"
    assert collectives.async_collective_options(
        Mesh(np.array(devices), ("data",))) == collectives.ASYNC_ALL_TO_ALL
    assert collectives.async_collective_options(
        Mesh(np.array(devices[:1]), ("data",))) is None
    assert collectives.async_collective_options(
        Mesh(np.array(jax.devices("cpu")[:4]), ("data",))) is None


def _all_to_alls(text):
    """``(pairs, made synchronous again, never asynchronous)``: XLA's
    scheduler turns a pair with nothing to fly under back into one op
    and leaves ``async_collective_name`` on it."""
    ops = [line for line in text.splitlines() if " all-to-all(" in line]
    again = sum("async_collective_name" in line for line in ops)
    return text.count(" all-to-all-start("), again, len(ops) - again


def test_four_device_step_compiles_to_asynchronous_all_to_alls(topo):
    text = _compiled_step(list(topo.devices))
    pairs, again, never = _all_to_alls(text)
    # this toy step has nothing to fly the first one under
    assert pairs >= 1 and pairs + again == 2 and never == 0
    assert text.count(" all-to-all-done(") == pairs
    # the vector and the loss still cross as all-reduces
    assert " all-reduce(" in text


def test_one_device_step_holds_no_collective_of_the_reduction(topo):
    text = _compiled_step(list(topo.devices)[:1])
    assert "all-to-all" not in text


def test_without_the_option_the_all_to_alls_are_synchronous(topo):
    """What the option buys, and that this compiler still needs it: the
    same reduction jitted without it holds two synchronous all-to-alls."""
    from jax import shard_map

    mesh = Mesh(np.array(list(topo.devices)), ("data",))
    fn = shard_map(
        lambda g: collectives.all_to_all_mean(g, "data"), mesh=mesh,
        in_specs=P(), out_specs=P(), check_vma=False)
    g = jax.ShapeDtypeStruct((1024, 512), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    assert _all_to_alls(jax.jit(fn).lower(g).compile().as_text()) == (0, 0, 2)
    pairs, again, never = _all_to_alls(
        jax.jit(fn, compiler_options=collectives.ASYNC_ALL_TO_ALL)
        .lower(g).compile().as_text())
    assert pairs + again == 2 and never == 0


@pytest.mark.parametrize("shape,dtype", [
    ((2, 8192, 2048, 3), jnp.bfloat16),  # the LFM2 cell's
    ((1, 64, 128, 4), jnp.float32),
], ids=["cell_bf16", "small_f32"])
@pytest.mark.parametrize("kernel", [short_conv.FWD, short_conv.BWD])
def test_mosaic_compiles_the_short_conv_kernels(kernel, shape, dtype, topo):
    from jax.sharding import SingleDeviceSharding

    B, T, D, L = shape
    one_chip = SingleDeviceSharding(topo.devices[0])
    geometry = short_conv._geometry(T, D, L, dtype)
    bcx = jax.ShapeDtypeStruct((B, T, 3 * D), dtype, sharding=one_chip)
    taps = jax.ShapeDtypeStruct((L, D), jnp.float32, sharding=one_chip)
    dy = jax.ShapeDtypeStruct((B, T, D), dtype, sharding=one_chip)
    if kernel == short_conv.FWD:
        lowered = jax.jit(lambda a, w: short_conv._forward(
            a, w, geometry, False)).lower(bcx, taps)
    else:
        lowered = jax.jit(lambda a, w, g: short_conv._backward(
            a, w, g, geometry, False)).lower(bcx, taps, dy)
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"short_conv/{kernel}" in text


@pytest.mark.parametrize("shape", [
    (65536, 2048, 3584, 8),    # the LFM2 cell's gate|up: a quarter live
    (131072, 768, 2048, 16),   # the SDAR cell's down: an eighth live
    (131072, 2048, 2048, 64),  # the OLMoE cell's gate|up: no tail
], ids=["lfm2_gate_up", "sdar_down", "olmoe_gate_up"])
def test_mosaic_compiles_the_grouped_matmuls_kernels(shape, topo):
    """Forward and both gradients at the three cells' shapes, bf16 rows on
    float32 masters: the row products' tail items (a zero write under a
    ``pl.when``, a block of ``lhs`` that stays put) are Mosaic's to take,
    which the interpreter cannot vouch for."""
    from jax.sharding import SingleDeviceSharding

    from chainermn_tpu.ops.grouped_matmul import _grouped_matmul

    m, k, n, e = shape
    one_chip = SingleDeviceSharding(topo.devices[0])
    lhs = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((e, k, n), jnp.float32, sharding=one_chip)
    gs = jax.ShapeDtypeStruct((e,), jnp.int32, sharding=one_chip)

    def loss(a, b, sizes):
        out = _grouped_matmul(a, b, sizes, False)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, (0, 1))).lower(lhs, rhs, gs).compile(
    ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert " conditional(" not in text


@pytest.mark.parametrize("strict", [False, True], ids=["inclusive", "strict"])
def test_mosaic_compiles_the_flash_kernels_under_a_mask_by_blocks(strict,
                                                                  topo):
    """The SDAR cell's attention shape (8192 positions, 32 / 4 heads of
    128, bf16, blocks of 4), forward and the two backward kernels, under
    the inclusive mask (clean on clean) and the strict one (noised on
    clean): the stair's mask is a column of block starts compared along
    the keys, which the interpreter cannot vouch for."""
    from jax.sharding import SingleDeviceSharding

    from chainermn_tpu.ops.flash_attention import flash_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal=True, causal_block=4, causal_strict=strict,
            interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile(
    ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("widths", [(192, 128), (256, 128)],
                         ids=["dsv2_192_128_transposed",
                              "own_rows_256_128"])
def test_mosaic_compiles_the_flash_kernels_at_a_value_width_of_their_own(
        widths, topo):
    """The DeepSeek-V2-Lite cell's attention shape (8192 positions, 16
    heads, keys of 128 + 64 and values of 128, bf16), forward and the two
    backward kernels: 192 lanes are one and a half tiles, so the op takes
    the ``[B * H, T, D]`` form, a block spanning the array's last
    dimension; and keys of 256 on values of 128 in the projections' own
    rows, one head a grid step at two block widths."""
    from jax.sharding import SingleDeviceSharding

    from chainermn_tpu.ops.flash_attention import flash_attention

    D, Dv = widths
    one_chip = SingleDeviceSharding(topo.devices[0])
    qk = jax.ShapeDtypeStruct((1, 8192, 16, D), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 16, Dv), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=0.11472,
                               interpret=False).astype(jnp.float32).sum()

    grad = jax.grad(loss, (0, 1, 2))
    text = jax.jit(grad).lower(qk, qk, v).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert [g.shape for g in jax.eval_shape(grad, qk, qk, v)] == [
        qk.shape, qk.shape, v.shape]


def test_mosaic_compiles_the_in_block_calls_kernels(topo):
    """The SDAR cell's noised copy (8192 positions, 32 / 4 heads of 128,
    bf16, blocks of 4) as the in-block call sees it: 16 sequences of one
    512-row tile each, no causal mask, segment ids a row's block within
    its tile; forward, dq and dk/dv."""
    from jax.sharding import SingleDeviceSharding

    from chainermn_tpu.ops import block_diffusion as bd

    one_chip = SingleDeviceSharding(topo.devices[0])
    L, bl = 8192, 4
    kw = dict(bl=bl, t=bd.in_block_tile(L, bl), scale=128 ** -0.5,
              interpret=False)
    assert kw["t"] == 512
    q = jax.ShapeDtypeStruct((1, L, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, L, 4, 128), jnp.bfloat16,
                              sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, 32, L), jnp.float32, sharding=one_chip)
    call = 'custom_call_target="tpu_custom_call"'
    fwd = jax.jit(lambda q, k, v: bd.in_block_fwd(q, k, v, **kw)).lower(
        q, kv, kv).compile().as_text()
    assert fwd.count(call) == 1
    bwd = jax.jit(lambda *a: bd.in_block_bwd(*a, **kw)).lower(
        q, kv, kv, q, lse, q).compile().as_text()
    assert bwd.count(call) == 2


def test_a_small_sdar_step_holds_nine_mosaic_calls_a_layer(topo,
                                                           monkeypatch):
    """The gradient of a two-layer SDAR of heads of 128, 8 query heads a
    key-value head, at ``L`` 1024 under remat ``dots``: under
    ``bd_attention`` three forward kernels a layer (clean on clean,
    noised on clean, in-block) and six backward ones, none run again by
    the recomputation; and no float32 sum over the head width of a
    ``[B, L, Hkv, G, D]`` array, which is what the in-block part's
    products were before they went through the kernels."""
    from jax.sharding import SingleDeviceSharding

    from chainermn_tpu.models import (
        MODEL_CONFIGS,
        lm_from_config,
        lm_loss_block_diffusion,
    )

    for name in ("flash_attention", "grouped_matmul", "block_diffusion"):
        monkeypatch.setattr(
            importlib.import_module(f"chainermn_tpu.ops.{name}"),
            "_use_interpret", lambda: False)
    one_chip = SingleDeviceSharding(topo.devices[0])
    L, layers = 1024, 2
    config = dict(
        MODEL_CONFIGS["sdar-30b-a3b"], num_hidden_layers=layers,
        hidden_size=256, num_attention_heads=8, num_key_value_heads=1,
        head_dim=128, moe_intermediate_size=128, num_experts=4,
        experts_published=8, experts_held_range=[2, 6],
        num_experts_per_tok=2, vocab_size=512, mask_token_id=511,
        max_position_embeddings=L)
    model = lm_from_config(config, compute_dtype=jnp.bfloat16,
                           return_hidden=True, remat=True)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: model.init(
        jax.random.key(1), jnp.zeros((1, 2 * L), jnp.int32))["params"]))
    lowered = jax.jit(jax.grad(lambda p, b, k: lm_loss_block_diffusion(
        model, p, b, k, mask_id=511, n_chunks=2)[0])).lower(
        params, placed(jax.ShapeDtypeStruct((1, L), jnp.int32)),
        placed(jax.eval_shape(lambda: jax.random.key(0))))
    calls = [line for line in lowered.compile().as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "bd_attention" in line]
    assert len(calls) == 9 * layers
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(f"/{kernel}/pallas_call" in line
                   for line in calls) == 3 * layers, kernel
    lane_sums = [line for line in lowered.as_text().splitlines()
                 if "stablehlo.reduce" in line
                 and re.search(r"dimensions = \[4\] : \(tensor<1x1024x1x8x128xf32>",
                               line)]
    assert not lane_sums, lane_sums[:2]


def _straight_line_section(x, w_gate_up, w_down, routing):
    """A share's expert section as it was spelled before the rounds."""
    from chainermn_tpu.parallel import moe

    return moe.combine(moe.gated_experts(
        moe.dispatch(x, routing), w_gate_up, w_down, routing.group_sizes),
        routing)


@pytest.mark.parametrize("spelling", ["rounds", "straight_line"])
def test_a_small_share_step_holds_one_copy_of_the_expert_section(
        spelling, topo, monkeypatch):
    """The gradient of a three-layer LFM2 (two expert layers, 2 of 8
    experts held, 2,048 rows a layer in rounds of 1,024) under remat
    ``dots``: **eight** Mosaic calls named ``moe_experts`` a layer in either
    spelling (straight-line: 2 forward, 2 recomputed, 4 backward; in
    rounds: 2 in the forward's loop, 2 + 4 in the backward's, and the
    replay of the forward is dead code), every one of the rounds' inside a
    ``while``'s body, and no ``conditional`` anywhere in the step."""
    from jax.sharding import SingleDeviceSharding

    from chainermn_tpu.models import (
        MODEL_CONFIGS,
        ROUTER_STATE,
        lm_from_config,
        lm_loss_moe,
    )
    from chainermn_tpu.parallel import moe

    for name in ("flash_attention", "grouped_matmul"):
        monkeypatch.setattr(
            importlib.import_module(f"chainermn_tpu.ops.{name}"),
            "_use_interpret", lambda: False)
    if spelling == "straight_line":
        monkeypatch.setattr(moe, "experts_in_rounds", _straight_line_section)
    one_chip = SingleDeviceSharding(topo.devices[0])
    T, expert_layers = 1024, 2
    config = dict(
        MODEL_CONFIGS["lfm2-8b-a1b"], num_hidden_layers=3,
        num_dense_layers=1, layer_types=["conv", "full_attention", "conv"],
        hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=256, moe_intermediate_size=128, num_experts=2,
        experts_published=8, experts_held_range=[2, 4],
        num_experts_per_tok=2, vocab_size=512, max_position_embeddings=T)
    model = lm_from_config(config, compute_dtype=jnp.bfloat16,
                           return_hidden=True, remat=True)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    v = jax.eval_shape(lambda: model.init(
        jax.random.key(1), jnp.zeros((1, T), jnp.int32)))
    text = jax.jit(jax.grad(lambda p, s, b: lm_loss_moe(
        model, p, b, n_chunks=2, load_balance_coef=0.0, z_loss_coef=0.0,
        router_state=s)[0])).lower(
        placed(v["params"]), placed(v[ROUTER_STATE]),
        placed(jax.ShapeDtypeStruct((1, T), jnp.int32))).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "moe_experts" in line]
    assert len(calls) == 8 * expert_layers
    assert " conditional(" not in text
    in_a_loop = sum("_moe_dropless/while/body/" in line for line in calls)
    assert in_a_loop == (len(calls) if spelling == "rounds" else 0)
    if spelling == "rounds":
        assert not any("rematted_computation" in line for line in calls)
