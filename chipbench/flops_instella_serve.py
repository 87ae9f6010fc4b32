"""Operations and bytes that SERVING ``instella-moe-16b-a3b-serve`` requires,
from shapes alone (``flops.py``'s rules: a multiply-add is two operations,
nothing the program reports is counted, required work only: no padding of a
prefill bucket, no reserved and unused cache, no expert that no token chose).

A token goes through the ACTIVE parameters of every layer: the attention's
five matrices, the dense feed-forward in the leading layers, and in the
others the router, its ``num_experts_per_tok`` experts and the shared
experts; the head at the positions that emit a token.  Attention over a
context is counted in its plain per-head form (``qk_head_dim`` a score,
``v_head_dim`` a value, a head and pair): the absorbed form a latent cache
invites costs more operations a pair (``rank + rope`` and ``rank``) and is
the implementation's choice, so it is not what is required.
"""


def matmul_params(**c):
    """Matrix parameters a token is multiplied by: a layer of each kind,
    the head, and what one routed expert holds."""
    e, heads = c["hidden_size"], c["num_attention_heads"]
    qk, v, rank = c["qk_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    nope = qk - c["qk_rope_head_dim"]
    attn = (e * heads * qk                              # W_q
            + e * (rank + c["qk_rope_head_dim"])        # W_kva
            + rank * heads * (nope + v)                 # W_kvb
            + 2 * e * heads * v)                        # W_g, W_o
    expert = 3 * e * c["moe_intermediate_size"]
    dense_layers = c["first_k_dense_replace"]
    sparse_layers = c["num_hidden_layers"] - dense_layers
    dense = attn + 3 * e * c["intermediate_size"]
    shared = c["n_shared_experts"] * expert
    router = e * c["n_routed_experts"]
    sparse = attn + router + shared + c["num_experts_per_tok"] * expert
    head = e * c["vocab_size"]
    return {"attn": attn, "expert": expert, "head": head,
            "active_layers": dense_layers * dense + sparse_layers * sparse,
            # what a step reads whatever it routes: everything but the
            # routed experts (the embedding's rows are not a matmul)
            "outside_routed": (dense_layers * dense + sparse_layers
                               * (attn + router + shared) + head),
            "sparse_layers": sparse_layers}


def _score_flops(pairs, **c):
    """Scores and weighted values over ``pairs`` (query, key) pairs, a
    layer: per head ``qk`` + ``v`` multiply-adds a pair."""
    return (2.0 * c["num_attention_heads"]
            * (c["qk_head_dim"] + c["v_head_dim"]) * pairs
            * c["num_hidden_layers"])


def prefill_flops(prompt, **c):
    p = matmul_params(**c)
    return (2.0 * p["active_layers"] * prompt + 2.0 * p["head"]
            + _score_flops(prompt * (prompt + 1) / 2.0, **c))


def decode_flops(context, **c):
    p = matmul_params(**c)
    return (2.0 * (p["active_layers"] + p["head"])
            + _score_flops(context, **c))


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


def decode_step_bytes(live_tokens, experts_touched, *, weight_bytes=2,
                      cache_bytes=4, **c):
    """Least HBM traffic of ONE pooled decode step: every weight outside
    the routed experts once, a routed expert's three matrices for each
    expert a layer that a live token chose (``experts_touched``: summed
    over the layers), and the latent cache of the live tokens (``rank +
    rope`` numbers a token and layer) at the cache's own width."""
    p = matmul_params(**c)
    latent = ((c["kv_lora_rank"] + c["qk_rope_head_dim"])
              * c["num_hidden_layers"])
    return float(weight_bytes * (p["outside_routed"]
                                 + p["expert"] * experts_touched)
                 + cache_bytes * latent * live_tokens)
