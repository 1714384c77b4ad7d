"""Operations and bytes a piece of the model needs, from its shapes.

The yardstick: a program may change how it computes, never what these
return. Recomputation (remat) is the price a job pays and is not counted.
"""

from __future__ import annotations


def dense_lm_train_flops_per_token(n_params: int, n_layer: int,
                                   seq_len: int, d_model: int) -> float:
    """Model FLOPs of one token's forward and backward pass (the PaLM
    appendix's convention, ``bench.py:_bench_transformer``'s arithmetic):
    ``6 N`` for the matmul stack, tied head included, and ``6 L T d`` for
    causal attention's two score matmuls at half a square."""
    return 6.0 * n_params + 6.0 * n_layer * seq_len * d_model


def causal_attention_train_cost(batch: int, heads: int, seq_len: int,
                                head_dim: int, bytes_per_el: int = 2
                                ) -> tuple[float, float]:
    """(FLOPs, bytes) of causal attention, forward and backward, for one
    layer. Forward: QK^T and PV, 2 flops a multiply-add, half the square
    under the causal mask: ``2 B H T^2 D``. Backward: five matmuls of the
    same size (scores again, dV, dP, dQ, dK): 2.5x. Bytes are the least
    HBM traffic: forward reads Q, K, V and writes O; backward reads Q, K,
    V, O, dO and writes dQ, dK, dV (row statistics are T/D smaller and
    left out)."""
    fwd = 2.0 * batch * heads * seq_len * seq_len * head_dim
    flops = 3.5 * fwd
    tensor = batch * seq_len * heads * head_dim * bytes_per_el
    return flops, 12.0 * tensor


def conv_macs(out_h: int, out_w: int, k_h: int, k_w: int, c_in: int,
              c_out: int) -> int:
    """Multiply-adds of one convolution for one image."""
    return out_h * out_w * k_h * k_w * c_in * c_out


def resnet_bottleneck_forward_macs(stage_sizes, num_filters: int,
                                   image: int, num_classes: int) -> int:
    """Multiply-adds of one image's forward pass through a bottleneck
    ResNet with the standard stem (7x7/2 conv, 3x3/2 max-pool) whose
    blocks stride on the 3x3 (v1.5): convolutions and the classifier;
    BatchNorm, ReLU and pooling are not matmul work and are left out."""
    hw = image // 2
    macs = conv_macs(hw, hw, 7, 7, 3, num_filters)
    hw //= 2  # max-pool
    c_in = num_filters
    for i, blocks in enumerate(stage_sizes):
        f = num_filters * 2 ** i
        for j in range(blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            out = hw // stride
            macs += conv_macs(hw, hw, 1, 1, c_in, f)        # 1x1 at input res
            macs += conv_macs(out, out, 3, 3, f, f)         # 3x3, strided
            macs += conv_macs(out, out, 1, 1, f, 4 * f)     # 1x1 expand
            if j == 0:                                      # projection
                macs += conv_macs(out, out, 1, 1, c_in, 4 * f)
            c_in, hw = 4 * f, out
    return macs + c_in * num_classes


def roofline_seconds(flops: float, nbytes: float, peak: dict
                     ) -> tuple[float, str]:
    """The least time the chip could take, and which bound holds."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
