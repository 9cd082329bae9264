"""Operations and bytes of the served work, computed from shapes.

``model`` is the ``model`` block of a configuration file.  Counts follow
the work the algorithm needs, not what an implementation happens to do:

* a linear layer is ``d_in * d_out`` multiply-accumulates per token at
  its dense shape (the DBB wire prunes operands, but the chip's matrix
  unit computes the dense tile, so the dense shape is the work it does);
* attention of a query at position ``p`` reads ``p + 1`` keys and
  values; MLA counts its absorbed form (queries absorbed through the key
  half of ``kv_up``, scores and context over the latent, the context
  projected through the value half);
* the least bytes the paged-attention kernel must read for a query row
  are the int8 keys, values and per-token scales of its live context,
  plus its query and output in bfloat16.

An operation is two per multiply-accumulate.
"""

from __future__ import annotations

SCALE_BYTES = 4  # one f32 scale per cached token row and plane


def dense_macs_per_token(model: dict) -> int:
    d, f, h = model["d_model"], model["d_ff"], model["n_heads"]
    mlp = 3 * d * f
    m = model.get("mla")
    if m:
        qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
        attn = (d * m["q_lora_rank"] + m["q_lora_rank"] * h * qk
                + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                + h * m["v_head_dim"] * d)
    else:
        dh, kv = model["head_dim"], model["n_kv_heads"]
        attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    return model["n_layers"] * (attn + mlp) + d * model["vocab"]


def attn_macs_per_layer(model: dict, ctx: int) -> int:
    """One query token attending ``ctx`` keys, in one layer."""
    h = model["n_heads"]
    m = model.get("mla")
    if m:
        lora, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
        absorb = h * m["qk_nope_head_dim"] * lora + h * lora * m["v_head_dim"]
        return absorb + h * (lora + rope) * ctx + h * lora * ctx
    return 2 * h * model["head_dim"] * ctx


def kv_bytes_per_token(model: dict) -> int:
    """int8 cache bytes one token adds per layer that attention reads."""
    m = model.get("mla")
    if m:  # the latent row and its scale; values are its prefix
        return m["kv_lora_rank"] + m["qk_rope_head_dim"] + SCALE_BYTES
    return 2 * (model["n_kv_heads"] * model["head_dim"] + SCALE_BYTES)


def qo_bytes_per_token(model: dict) -> int:
    """bf16 query in and output out of the kernel, per token and layer."""
    h = model["n_heads"]
    m = model.get("mla")
    if m:
        lora = m["kv_lora_rank"]
        return 2 * h * (lora + m["qk_rope_head_dim"]) + 2 * h * lora
    return 2 * 2 * h * model["head_dim"]


def request_work(model: dict, prompt_len: int, n_out: int, cached: int,
                 chunk: int) -> dict:
    """Work one request makes the device do: prefill of the prompt past
    its ``cached`` prefix in chunks of ``chunk``, then ``n_out - 1`` fed
    decode tokens (the first output comes from the last prefill chunk).

    Returns ``ops`` (model operations), ``kv_bytes`` (least cache bytes
    paged attention reads), ``qo_bytes`` and ``tokens`` (positions run).
    """
    layers = model["n_layers"]
    dense = dense_macs_per_token(model)
    positions = list(range(cached, prompt_len + n_out - 1))
    macs = sum(dense + layers * attn_macs_per_layer(model, p + 1)
               for p in positions)
    # keys read per layer: a prefill chunk reads its context once, a
    # decode token reads its own
    keys = sum(min(a + chunk, prompt_len) for a in range(cached, prompt_len, chunk))
    keys += sum(p + 1 for p in range(prompt_len, prompt_len + n_out - 1))
    return {
        "ops": 2 * macs,
        "kv_bytes": layers * keys * kv_bytes_per_token(model),
        "qo_bytes": layers * len(positions) * qo_bytes_per_token(model),
        "tokens": len(positions),
    }
