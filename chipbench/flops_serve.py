"""Operations and bytes that SERVING a decoder-only LM requires, from shapes
alone (``flops.py``'s rules: a multiply-add is two operations, nothing the
program reports is counted, required work only: no padding of a prefill
bucket, no reserved and unused cache).

A prompt of P tokens: every position through the layers' matmuls, the head
at ONE position (the first token), attention over the causal, windowed
pairs.  A decoded token at context c (its own position included): the
layers' matmuls and the head once, attention over min(c, window) keys.
"""

from chipbench import flops


def prefill_flops(prompt, **cfg):
    p = flops.lm_matmul_params(**cfg)
    pairs = flops.attended_pairs(prompt, cfg.get("sliding_window"))
    attn = (4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
            * cfg["num_hidden_layers"])
    return (2.0 * cfg["num_hidden_layers"] * p["layer"] * prompt
            + 2.0 * p["head"] + attn)


def decode_flops(context, **cfg):
    p = flops.lm_matmul_params(**cfg)
    keys = min(context, cfg.get("sliding_window") or context)
    return (2.0 * p["all"] + 4.0 * cfg["num_attention_heads"]
            * cfg["head_dim"] * keys * cfg["num_hidden_layers"])


def window_flops(records, seconds, **cfg):
    """Required operations of the work whose token was stamped inside
    ``[0, seconds)``: a request's first stamp stands for its prefill, its
    j-th later stamp for a decode at context prompt + j."""
    total = 0.0
    for r in records:
        for j, stamp in enumerate(r.stamps):
            if 0.0 <= stamp < seconds:
                total += (prefill_flops(r.prompt_len, **cfg) if j == 0
                          else decode_flops(r.prompt_len + j, **cfg))
    return total


def decode_step_bytes(live_tokens, *, weight_bytes=2, cache_bytes=4, **cfg):
    """Least HBM traffic of ONE pooled decode step: every matmul weight
    once (bfloat16), and the keys and values of the tokens that are live
    (``live_tokens``: summed over the slots in use, each capped by the
    window by the caller) at the cache's own width.  Not the reserved
    slots: a step that reads them shows as a lower share."""
    p = flops.lm_matmul_params(**cfg)
    kv = (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
          * cfg["num_hidden_layers"])
    return float(weight_bytes * p["all"] + cache_bytes * kv * live_tokens)
