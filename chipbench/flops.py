"""Operations and bytes that the benchmark's work needs, from shapes alone.

The yardstick for ``mfu_pct.*`` and ``*_roofline_pct.*``: what the forward
and backward passes REQUIRE (a recomputation inside a kernel or under
``jax.checkpoint`` is not counted, so it shows as a lower share), counted
from the configuration's and the traffic's sizes and from nothing the
program reports.  ``Lowered.cost_analysis()`` is empty on the TPU client
(PERF.md, PR 21), so there is no compiler figure to lean on.

A multiply-add is two operations.  The backward pass of a matrix product
costs two products of the forward's size, so a train step is three times
the forward for every matmul and convolution.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peak_for(device_kind: str) -> dict:
    """The peaks row for ``device_kind``; an unknown device is an error."""
    with open(PEAKS_FILE) as f:
        table = {k: v for k, v in json.load(f).items()
                 if not k.startswith("_")}
    if device_kind not in table:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} in "
            f"{PEAKS_FILE} (known: {sorted(table)}); add a sourced row "
            "before benchmarking on it")
    return table[device_kind]


# --------------------------------------------------------------------------
# ResNet (models/resnet.py: NHWC, SAME padding, stride on the 3x3 conv)
# --------------------------------------------------------------------------


def _out(size: int, stride: int) -> int:
    return -(-size // stride)   # SAME padding


def resnet_forward_flops_per_image(*, image_size: int = 224,
                                   stage_sizes=(3, 4, 6, 3),
                                   num_filters: int = 64,
                                   num_classes: int = 1000,
                                   channels: int = 3) -> float:
    """Forward operations of the bottleneck ResNet for one image: every
    convolution and the classifier as 2 x multiply-adds; BatchNorm, ReLU
    and pooling (under 1% of the total) are left out."""
    flops = 0.0

    def conv(hw, cin, cout, k):
        return 2.0 * hw * hw * cin * cout * k * k

    hw = _out(image_size, 2)                       # 7x7 stride 2 stem
    flops += conv(hw, channels, num_filters, 7)
    hw = _out(hw, 2)                               # 3x3 stride 2 max-pool
    cin = num_filters
    for i, blocks in enumerate(stage_sizes):
        f = num_filters * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            hw_out = _out(hw, stride)
            flops += conv(hw, cin, f, 1)           # 1x1 at the input size
            flops += conv(hw_out, f, f, 3)         # 3x3 carries the stride
            flops += conv(hw_out, f, 4 * f, 1)
            if stride != 1 or cin != 4 * f:        # projection shortcut
                flops += conv(hw_out, cin, 4 * f, 1)
            hw, cin = hw_out, 4 * f
    return flops + 2.0 * cin * num_classes


def resnet_train_flops_per_image(**sizes) -> float:
    """Forward + backward: three times the forward."""
    return 3.0 * resnet_forward_flops_per_image(**sizes)


# --------------------------------------------------------------------------
# Transformer LM (models/transformer.py: GQA, one window, ratio-4 MLP,
# untied head through the fused linear+cross-entropy kernel)
# --------------------------------------------------------------------------


def attended_pairs(seq: int, window=None) -> int:
    """Query-key pairs a causal (optionally sliding-window) mask keeps in
    one sequence: query t sees min(t + 1, window) keys."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def lm_matmul_params(*, hidden_size, num_hidden_layers, num_attention_heads,
                     num_key_value_heads, head_dim, intermediate_size,
                     vocab_size, **_) -> dict:
    """Weights that take part in a matrix product, per layer and for the
    head (the embedding table is a gather)."""
    e, h, hkv, d = (hidden_size, num_attention_heads, num_key_value_heads,
                    head_dim)
    layer = (e * h * d + e * 2 * hkv * d + h * d * e
             + 2 * e * intermediate_size)
    return {"layer": layer, "head": e * vocab_size,
            "all": num_hidden_layers * layer + e * vocab_size}


def flash_train_flops(*, batch, seq, num_attention_heads, head_dim,
                      sliding_window=None, layers=1, **_) -> float:
    """Attention's required operations, forward and backward, for
    ``batch`` sequences through ``layers`` layers: QK^T and PV forward
    (4 x H x D a pair), dV, dP, dQ, dK backward (8).  The score
    recomputation of a flash backward is not counted."""
    pairs = batch * attended_pairs(seq, sliding_window)
    return 12.0 * num_attention_heads * head_dim * pairs * layers


def flash_train_bytes(*, batch, seq, num_attention_heads,
                      num_key_value_heads, head_dim, layers=1,
                      bytes_per_element=4, **_) -> float:
    """Least HBM traffic of attention forward and backward: q, k, v, o
    and their four cotangents each cross once, q/k/v/o/do are read again
    by the backward.  The model hands the kernel float32 q, k, v."""
    q = batch * seq * num_attention_heads * head_dim
    kv = batch * seq * num_key_value_heads * head_dim
    fwd = 2 * q + 2 * kv                 # read q, k, v; write o
    bwd = 3 * q + 2 * kv + q + 2 * kv    # read q, o, do, k, v; write dq, dk, dv
    return float(bytes_per_element * (fwd + bwd) * layers)


def xent_train_flops(*, rows, hidden_size, vocab_size, **_) -> float:
    """Fused linear + cross-entropy on ``rows`` positions: logits forward,
    d(activations) and d(head) backward, 2 x rows x E x V each."""
    return 6.0 * rows * hidden_size * vocab_size


def xent_train_bytes(*, rows, hidden_size, vocab_size, **_) -> float:
    """Least traffic: bf16 activations and head read by each of the three
    products, d(activations) written in bf16 and d(head) in float32."""
    x, w = rows * hidden_size, hidden_size * vocab_size
    return float(3 * 2 * (x + w) + 2 * x + 4 * w)


def lm_train_flops_per_token(*, seq, **cfg) -> float:
    """Required forward + backward operations per trained token: 6 x the
    matmul weights, plus attention's share of one sequence."""
    p = lm_matmul_params(**cfg)["all"]
    attn = flash_train_flops(
        batch=1, seq=seq, layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg.get("sliding_window"))
    return 6.0 * p + attn / seq
