"""Operations and bytes that SERVING ``k-exaone-236b-a23b-serve`` requires,
from shapes alone (``flops.py``'s rules: a multiply-add is two operations,
nothing the program reports is counted, required work only: no padding of a
prefill bucket, no reserved and unused cache, no expert that no token chose).

A token goes through every layer's attention (q, k, v, o, and its scores:
over the last ``sliding_window`` positions in a window layer, over the whole
context in a global one) and feed-forward: the dense SwiGLU in the leading
layers; in the others the router, the shared expert and, of its
``num_experts_per_tok`` routed experts, the share this chip holds at its
expectation ``k x held / router_width`` (as ``flops_nemotron_h_serve.py``
counts a share); the head, over this chip's slice of the vocabulary, at the
positions that emit a token.  Norms and the gate's sigmoid are not counted.
"""


def layers_of(**c):
    """Per-layer kinds of the layers held here: (windowed, sparse) a layer."""
    n, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    return [(bool(c["window_layout"][i]), i >= dense) for i in range(n)]


def matmul_params(**c):
    """Matrix parameters: a layer's attention, its dense feed-forward, one
    routed expert, what an expert layer holds outside its routed experts,
    the head; what a token is multiplied by (``active_layers``) and what a
    step reads whatever it routes (``outside_routed``)."""
    e, d = c["hidden_size"], c["head_dim"]
    attn = (2 * e * c["num_attention_heads"] * d
            + 2 * e * c["num_key_value_heads"] * d)
    dense = 3 * e * c["intermediate_size"]
    expert = 3 * e * c["moe_intermediate_size"]
    outside = e * c["router_width"] + c["num_shared_experts"] * expert
    held_routes = (c["num_experts_per_tok"] * c["num_experts"]
                   / c["router_width"])
    kinds = layers_of(**c)
    n_sparse = sum(sparse for _, sparse in kinds)
    n_dense = len(kinds) - n_sparse
    head = e * c["vocab_size"]
    return {"attn": attn, "dense": dense, "expert": expert, "head": head,
            "active_layers": (len(kinds) * attn + n_dense * dense
                              + n_sparse * (outside + held_routes * expert)),
            "outside_routed": (len(kinds) * attn + n_dense * dense
                               + n_sparse * outside + head)}


def parameters(**c):
    """Every parameter this chip holds: the matrices, the held experts, the
    router's bias, the norms (two a block, q's and k's, the final one) and
    the embedding beside the head."""
    p = matmul_params(**c)
    e = c["hidden_size"]
    kinds = layers_of(**c)
    n_sparse = sum(sparse for _, sparse in kinds)
    return (p["outside_routed"] + n_sparse * (c["num_experts"] * p["expert"]
                                              + c["router_width"])
            + len(kinds) * (2 * e + 2 * c["head_dim"]) + e + p["head"])


def _score_flops(pairs_window, pairs_full, **c):
    """Scores and weighted values: ``pairs_*`` (query, key) pairs in a
    window layer and in a global one, 4 x heads x head_dim operations a
    pair (q.k and p.v)."""
    kinds = layers_of(**c)
    n_window = sum(w for w, _ in kinds)
    return (4.0 * c["num_attention_heads"] * c["head_dim"]
            * (n_window * pairs_window
               + (len(kinds) - n_window) * pairs_full))


def prefill_flops(prompt, **c):
    w = c["sliding_window"]
    seen = (w * (w + 1) / 2.0 + (prompt - w) * w if prompt > w
            else prompt * (prompt + 1) / 2.0)
    p = matmul_params(**c)
    return (2.0 * p["active_layers"] * prompt + 2.0 * p["head"]
            + _score_flops(seen, prompt * (prompt + 1) / 2.0, **c))


def decode_flops(context, **c):
    p = matmul_params(**c)
    return (2.0 * (p["active_layers"] + p["head"])
            + _score_flops(min(context, c["sliding_window"]), context, **c))


def window_flops(records, seconds, **c):
    """Required operations of the work whose token was stamped inside
    ``[0, seconds)``: a request's first stamp stands for its prefill, its
    j-th later stamp for a decode at context prompt + j."""
    total = 0.0
    for r in records:
        for j, stamp in enumerate(r.stamps):
            if 0.0 <= stamp < seconds:
                total += (prefill_flops(r.prompt_len, **c) if j == 0
                          else decode_flops(r.prompt_len + j, **c))
    return total


def kv_bytes_per_row(*, cache_bytes=4, **c):
    """A layer's key and value of one position."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * cache_bytes


def decode_step_bytes(live_tokens, ring_rows, experts_touched, *,
                      weight_bytes=2, cache_bytes=4, **c):
    """Least HBM traffic of ONE pooled decode step: every weight outside the
    routed experts once; a routed expert's three matrices for each HELD
    expert a layer that a live token chose (``experts_touched``: summed over
    the layers); the live ring rows of the window layers (``ring_rows``:
    summed over the live slots and those layers, ``min(depth + 1, window)``
    a slot and layer); the global layers' keys and values of the live tokens
    (``live_tokens``: summed over the live slots), at the cache's width."""
    p = matmul_params(**c)
    n_full = sum(not w for w, _ in layers_of(**c))
    row = kv_bytes_per_row(cache_bytes=cache_bytes, **c)
    return float(weight_bytes * (p["outside_routed"]
                                 + p["expert"] * experts_touched)
                 + row * (ring_rows + n_full * live_tokens))
