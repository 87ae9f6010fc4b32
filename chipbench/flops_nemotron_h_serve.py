"""Operations and bytes that SERVING ``nemotron3-super-120b-a12b-serve``
requires, from shapes alone (``flops.py``'s rules: a multiply-add is two
operations, nothing the program reports is counted, required work only: no
padding of a prefill bucket, no reserved and unused cache, no expert that no
token chose, and the state-space recurrence as WRITTEN, one update a token,
not the chunked form a prompt happens to run in).

A token goes through every layer of the pattern: a mixer's two projections,
its convolution and its recurrence; the attention's four matrices and its
scores over the context; an expert layer's router, latent projections and
shared expert, and of its ``num_experts_per_tok`` routed experts the share
this chip holds, at its expectation ``k x held / router_width`` (as
``flops_smallthinker.py`` counts a share); the head, over this chip's slice
of the vocabulary, at the positions that emit a token.
"""


def sizes_of(**c):
    heads, p = c["mamba_num_heads"], c["mamba_head_dim"]
    inner = heads * p
    return {"inner": inner,
            "wide": inner + 2 * c["n_groups"] * c["ssm_state_size"],
            "state": heads * p * c["ssm_state_size"],
            "layers": {k: c["hybrid_override_pattern"].count(k)
                       for k in "M*E"}}


def matmul_params(**c):
    """Matrix parameters a token is multiplied by, a layer of each kind,
    the head, one routed expert, and what a step reads whatever it
    routes."""
    e, s = c["hidden_size"], sizes_of(**c)
    mixer = e * (s["inner"] + s["wide"] + c["mamba_num_heads"]) \
        + s["inner"] * e
    qo = c["num_attention_heads"] * c["head_dim"]
    attn = 2 * e * qo + 2 * e * c["num_key_value_heads"] * c["head_dim"]
    expert = 2 * c["moe_latent_size"] * c["moe_intermediate_size"]
    outside = (e * c["router_width"] + 2 * e * c["moe_latent_size"]
               + 2 * e * c["moe_shared_expert_intermediate_size"])
    held_routes = (c["num_experts_per_tok"] * c["n_routed_experts"]
                   / c["router_width"])
    n = s["layers"]
    head = e * c["vocab_size"]
    return {"mixer": mixer, "attn": attn, "expert": expert, "head": head,
            "active_layers": (n["M"] * mixer + n["*"] * attn
                              + n["E"] * (outside + held_routes * expert)),
            "outside_routed": (n["M"] * mixer + n["*"] * attn
                               + n["E"] * outside + head)}


def _token_flops(**c):
    """A token's operations outside the attention's scores: the matmuls, the
    convolution's taps and the recurrence (decay, outer product, add, and
    the read-out: 6 a state element)."""
    s = sizes_of(**c)
    return (2.0 * matmul_params(**c)["active_layers"]
            + s["layers"]["M"] * (6.0 * s["state"]
                                  + 2.0 * c["conv_kernel"] * s["wide"]))


def _score_flops(pairs, **c):
    """Scores and weighted values over ``pairs`` (query, key) pairs."""
    return (4.0 * c["num_attention_heads"] * c["head_dim"] * pairs
            * sizes_of(**c)["layers"]["*"])


def prefill_flops(prompt, **c):
    return (_token_flops(**c) * prompt + 2.0 * matmul_params(**c)["head"]
            + _score_flops(prompt * (prompt + 1) / 2.0, **c))


def decode_flops(context, **c):
    return (_token_flops(**c) + 2.0 * matmul_params(**c)["head"]
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


def state_bytes_per_slot(*, cache_bytes=4, **c):
    """A slot's recurrent state: every mixer's ``[heads, head_dim, state]``
    and the last ``conv_kernel - 1`` inputs of its convolution."""
    s = sizes_of(**c)
    return cache_bytes * s["layers"]["M"] * (
        s["state"] + (c["conv_kernel"] - 1) * s["wide"])


def decode_step_bytes(live_tokens, experts_touched, live_slots, *,
                      weight_bytes=2, cache_bytes=4, **c):
    """Least HBM traffic of ONE pooled decode step: every weight outside
    the routed experts once; a routed expert's two matrices for each HELD
    expert a layer that a live token chose (``experts_touched``: summed
    over the layers); each live slot's recurrent state once read and once
    written; the live tokens' keys and values (one attention layer)."""
    p = matmul_params(**c)
    kv = (2 * c["num_key_value_heads"] * c["head_dim"]
          * sizes_of(**c)["layers"]["*"])
    return float(weight_bytes * (p["outside_routed"]
                                 + p["expert"] * experts_touched)
                 + 2 * state_bytes_per_slot(cache_bytes=cache_bytes, **c)
                 * live_slots
                 + cache_bytes * kv * live_tokens)
