"""Operations and bytes that SmallThinker-21BA3B-Instruct's train step
needs, from shapes alone (``flops.py`` says how they are counted: what the
passes REQUIRE, a multiply-add is two operations, backward is twice
forward).  Beside ``flops.py`` what differs: attention has a per-layer
layout (a layer with 0 in ``sliding_window_layout`` is full), the
feed-forward is the held experts' share of a top-k expert layer, the head
is the vocabulary slice's.

The experts' count is that of the routes HELD, whoever implements the
grouped products: ``routes_held`` where the caller has what the step
counted (one value a layer: the grouped products' roofline), else the
EXPECTED one under uniform routing, where a token sends ``k * held /
router_width`` of its routes to an expert held here (1.5 of 6 for 16 of
64: the whole step's operations, which are the deployment's and not a
run's).
"""

from chipbench import flops


def matmul_params(*, hidden_size, num_attention_heads, num_key_value_heads,
                  head_dim, moe_ffn_hidden_size, router_width,
                  moe_num_primary_experts, moe_num_active_primary_experts,
                  vocab_size, **_) -> dict:
    """Weights a token meets in a matrix product, per layer and in the
    head; the experts' at the expected number of routes held."""
    e, h, hkv, d = (hidden_size, num_attention_heads, num_key_value_heads,
                    head_dim)
    routes = (moe_num_active_primary_experts * moe_num_primary_experts
              / router_width)
    return {"projections": e * h * d + e * 2 * hkv * d + h * d * e,
            "router": e * router_width,
            "experts": routes * 3 * e * moe_ffn_hidden_size,
            "head": e * vocab_size}


def flash_mixed_train_flops(*, batch, seq, num_attention_heads, head_dim,
                            sliding_window_size, sliding_window_layout,
                            **_) -> float:
    """``flops.flash_train_flops`` summed over the layout's layers."""
    return sum(flops.flash_train_flops(
        batch=batch, seq=seq, num_attention_heads=num_attention_heads,
        head_dim=head_dim,
        sliding_window=sliding_window_size if windowed else None)
        for windowed in sliding_window_layout)


def flash_mixed_train_bytes(*, sliding_window_layout, **sizes) -> float:
    """Least traffic: every layer moves q, k, v, o and their cotangents
    once whatever its mask."""
    return flops.flash_train_bytes(
        **{**sizes, "layers": len(sliding_window_layout)})


def held_routes(*, batch, seq, moe_num_active_primary_experts,
                moe_num_primary_experts, router_width, **_) -> float:
    """Expected routes a step sends to the experts held, per layer."""
    return (batch * seq * moe_num_active_primary_experts
            * moe_num_primary_experts / router_width)


def routes_all_layers(*, sliding_window_layout, routes_held=None,
                      **sizes) -> float:
    """Routes a step sends to the experts held, summed over the layers:
    as counted (``routes_held``, one value a layer) or as expected."""
    if routes_held:
        return float(sum(routes_held))
    return held_routes(**sizes) * len(sliding_window_layout)


def experts_train_flops(*, hidden_size, moe_ffn_hidden_size,
                        **sizes) -> float:
    """Gate, up and down products forward and their six backward
    products: 18 x hidden x width operations a route."""
    return (18.0 * hidden_size * moe_ffn_hidden_size
            * routes_all_layers(**sizes))


def experts_train_bytes(*, hidden_size, moe_ffn_hidden_size,
                        moe_num_primary_experts, sliding_window_layout,
                        **sizes) -> float:
    """Least traffic of the grouped products: a route's bf16 row in and
    out forward, row, cotangent in and cotangent out backward; the held
    experts' bf16 weights read forward and backward, their gradient
    written in float32.  The intermediate of width ``moe_ffn_hidden_size``
    crosses HBM only because the three products are separate kernels."""
    rows = 5 * 2 * hidden_size * routes_all_layers(
        moe_num_primary_experts=moe_num_primary_experts,
        sliding_window_layout=sliding_window_layout, **sizes)
    weights = (2 + 2 + 4) * 3 * (moe_num_primary_experts * hidden_size
                                 * moe_ffn_hidden_size)
    return float(rows + weights * len(sliding_window_layout))


def train_flops_per_token(*, seq, **cfg) -> float:
    """Required forward + backward operations per trained token."""
    p = matmul_params(**cfg)
    layers = len(cfg["sliding_window_layout"])
    matmul = layers * (p["projections"] + p["router"] + p["experts"]) \
        + p["head"]
    return 6.0 * matmul + flash_mixed_train_flops(batch=1, seq=seq,
                                                  **cfg) / seq
