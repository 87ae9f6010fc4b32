"""Operations and bytes that SERVING ``jamba2-3b-serve`` requires, from shapes
alone (``flops.py``'s rules: a multiply-add is two operations, nothing the
program reports is counted, required work only: no padding of a prefill
bucket, no reserved and unused cache, and the selective scan as WRITTEN, one
update a token).

A token goes through every layer of the pattern: the mixer of its kind (a
Mamba-1 mixer's four projections, its convolution and its recurrence, or the
attention's four matrices and its scores over the context) and the gated
feed-forward's three matrices; the head, which is the embedding, once at the
positions that emit a token.  What is counted is the MATRIX products'
operations (what the matrix unit's peak is a peak of: ``mfu_pct.srv``); the
scan's elementwise work (the vector unit's: an ``exp``, the decay, the outer
product, the add and the read-out, 7 a state element) is in NO total here,
because no share of the matrix peak may claim it, and its bound is the
state's bytes (``ssm_state_bytes_per_slot``).
"""


def sizes_of(**c):
    inner = c["mamba_expand"] * c["hidden_size"]
    return {"inner": inner,
            "state": inner * c["mamba_d_state"],
            "layers": {k: c["layer_pattern"].count(k) for k in "ma"}}


def matmul_params(**c):
    """Matrix parameters a token is multiplied by: a mixer of each kind, the
    feed-forward, the head (the embedding, read once), and all layers."""
    e, s = c["hidden_size"], sizes_of(**c)
    di, n, r = s["inner"], c["mamba_d_state"], c["mamba_dt_rank"]
    mixer = e * 2 * di + di * (r + 2 * n) + r * di + di * e
    qo = c["num_attention_heads"] * c["head_dim"]
    attn = 2 * e * qo + 2 * e * c["num_key_value_heads"] * c["head_dim"]
    ffn = 3 * e * c["intermediate_size"]
    layers = s["layers"]
    return {"mixer": mixer, "attn": attn, "ffn": ffn,
            "head": e * c["vocab_size"],
            "layers": (layers["m"] * mixer + layers["a"] * attn
                       + (layers["m"] + layers["a"]) * ffn)}


def parameters(**c):
    """Every parameter of the model, the vectors too (the tied embedding
    once): 3,029,337,472 at the published sizes."""
    e, s = c["hidden_size"], sizes_of(**c)
    di, n, r = s["inner"], c["mamba_d_state"], c["mamba_dt_rank"]
    m = matmul_params(**c)
    layers = s["layers"]
    vectors = (c["mamba_d_conv"] * di + di      # the taps and their bias
               + di + di * n + di               # dt bias, A_log, D
               + r + 2 * n)                     # the three inner norms
    return (m["layers"] + m["head"] + layers["m"] * vectors
            + 2 * e * (layers["m"] + layers["a"]) + e)


def _token_flops(**c):
    """A token's matrix operations outside the attention's scores and the
    head, with the convolution's taps."""
    s = sizes_of(**c)
    return (2.0 * matmul_params(**c)["layers"]
            + s["layers"]["m"] * 2.0 * c["mamba_d_conv"] * s["inner"])


def _score_flops(pairs, **c):
    """Scores and weighted values over ``pairs`` (query, key) pairs."""
    return (4.0 * c["num_attention_heads"] * c["head_dim"] * pairs
            * sizes_of(**c)["layers"]["a"])


def prefill_flops(prompt, **c):
    return (_token_flops(**c) * prompt + 2.0 * matmul_params(**c)["head"]
            + _score_flops(prompt * (prompt + 1) / 2.0, **c))


def decode_flops(context, **c):
    return (_token_flops(**c) + 2.0 * matmul_params(**c)["head"]
            + _score_flops(context, **c))


def window_flops(records, seconds, **c):
    """Required MATRIX operations of the work whose token was stamped inside
    ``[0, seconds)``: a request's first stamp stands for its prefill, its
    j-th later stamp for a decode at context prompt + j."""
    total = 0.0
    for r in records:
        for j, stamp in enumerate(r.stamps):
            if 0.0 <= stamp < seconds:
                total += (prefill_flops(r.prompt_len, **c) if j == 0
                          else decode_flops(r.prompt_len + j, **c))
    return total


def ssm_state_bytes_per_slot(*, cache_bytes=4, **c):
    """The recurrent states alone, every mixer's ``[state, channels]``: what
    a recurrent update reads and writes."""
    s = sizes_of(**c)
    return cache_bytes * s["layers"]["m"] * s["state"]


def state_bytes_per_slot(*, cache_bytes=4, **c):
    """A slot's whole state: every mixer's recurrent state and the last
    ``mamba_d_conv - 1`` inputs of its convolution: 10,117,120 B at the
    published sizes in float32."""
    s = sizes_of(**c)
    return (ssm_state_bytes_per_slot(cache_bytes=cache_bytes, **c)
            + cache_bytes * s["layers"]["m"]
            * (c["mamba_d_conv"] - 1) * s["inner"])


def decode_step_bytes(live_slots, live_tokens, *, weight_bytes=2,
                      cache_bytes=4, **c):
    """Least HBM traffic of ONE pooled decode step: every weight once (the
    embedding is read once, as the head; a token's own row of it is
    nothing); each live slot's state once read and once written; the live
    tokens' keys and values (the attention layers' one head)."""
    kv = (2 * c["num_key_value_heads"] * c["head_dim"]
          * sizes_of(**c)["layers"]["a"])
    return float(weight_bytes * parameters(**c)
                 + 2 * state_bytes_per_slot(cache_bytes=cache_bytes, **c)
                 * live_slots
                 + cache_bytes * kv * live_tokens)
