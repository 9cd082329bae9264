"""Least time of the DBB linears in one pass of the model, from shapes.

A dispatch of the serving loop runs every linear of the model once over
the positions it feeds: a mixed step over its live chunk positions, a
fused decode iteration over its live rows (the loop runs the stack once
an iteration, so it reads every weight again each time).  The chip
computes each linear at its dense shape (``counts.dense_macs_per_token``,
the same count ``mfu`` uses) and must read each packed weight once a
pass: int8 values of ``nnz`` of every ``bz`` rows, a one-byte bitmask
per block of ``bz`` rows, and one f32 scale per output channel
(``kernels/ref.pack_weight_int8``).  The least time of the pass is the
larger of its operations over the int8 peak and those bytes over the
HBM bandwidth.  The served wire is 4 of 8 (README, PERF.md §1).
"""

from __future__ import annotations

from typing import List, Tuple

from bench import counts

NNZ, BZ = 4, 8
SCALE_BYTES = 4


def linear_shapes(model: dict) -> List[Tuple[int, int]]:
    """``(d_in, d_out)`` of every packed linear of the model; the head's
    ``d_out`` is the padded vocabulary it stores."""
    d, f, h = model["d_model"], model["d_ff"], model["n_heads"]
    m = model.get("mla")
    if m:  # kv_up stays dense (engine.pack_params_for_serving)
        qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
        attn = [(d, m["q_lora_rank"]), (m["q_lora_rank"], h * qk),
                (d, m["kv_lora_rank"] + m["qk_rope_head_dim"]),
                (h * m["v_head_dim"], d)]
    else:
        dh, kv = model["head_dim"], model["n_kv_heads"]
        attn = [(d, h * dh), (d, kv * dh), (d, kv * dh), (h * dh, d)]
    layer = attn + [(d, f), (d, f), (f, d)]
    return layer * model["n_layers"] + [(d, model["padded_vocab"])]


def packed_weight_bytes(model: dict, nnz: int = NNZ, bz: int = BZ) -> int:
    """Bytes of every packed linear weight on the int8 wire."""
    return sum(k * n * nnz // bz + (k // bz) * n + SCALE_BYTES * n
               for k, n in linear_shapes(model))


def least_linear_s(model: dict, positions: int, peaks: dict) -> float:
    """Least seconds one pass of the linears over ``positions`` live
    positions takes on a chip with ``peaks`` (``peaks.json``)."""
    ops = 2 * positions * counts.dense_macs_per_token(model)
    return max(ops / peaks["int8_ops"],
               packed_weight_bytes(model) / peaks["hbm_bytes_per_s"])
