"""Work counted from shapes: FLOPs and bytes of one PIM linear call, the
model FLOPs of served tokens, and the table of device peaks.

Everything here is arithmetic on sizes, kept with the benchmark so that no
change to the program can change how its work is counted.  Bytes are
counted at the configuration's stated dtypes (bf16 weights, activations and
outputs), not at what one implementation happens to move: a kernel that
reads less can then not pass 100% of its roofline.

The parameter and FLOP arithmetic is copied from the dense-attention part
of ``repro.telemetry.roofline`` (``count_params`` / ``model_flops``).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peak rates of ``device_kind`` (as ``jax.Device.device_kind``
    names it).  A device that is not in the table is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def linear_work(m: int, k: int, n: int, dtype_bytes: int = 2):
    """(FLOPs, bytes) of ``(m, k) @ (k, n)``: every operand read once and
    the output written once, at ``dtype_bytes`` each."""
    return 2.0 * m * k * n, float(dtype_bytes) * (m * k + k * n + m * n)


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def layer_params(d: dict) -> float:
    """Parameters of one dense attention + gated-MLP layer."""
    dm, hd = d["d_model"], d["head_dim"]
    attn = dm * d["n_heads"] * hd + 2 * dm * d["n_kv_heads"] * hd \
        + d["n_heads"] * hd * dm
    return float(attn + 3 * dm * d["d_ff"])


def count_params(d: dict) -> float:
    """All parameters: layers, embedding and an untied head."""
    return d["n_layers"] * layer_params(d) + 2.0 * d["vocab"] * d["d_model"]


def token_flops(d: dict, context: int) -> float:
    """Forward FLOPs of one token through the layers that attends over
    ``context`` positions (2 per parameter, 4·context·H·hd for QK and PV);
    the head is counted apart, since the serving path runs it once per
    call row and not once per prompt token."""
    attn = 4.0 * context * d["n_heads"] * d["head_dim"]
    return d["n_layers"] * (2.0 * layer_params(d) + attn)


def head_flops(d: dict) -> float:
    return 2.0 * d["d_model"] * d["vocab"]


def served_flops(d: dict, prompt_lens, decode_contexts) -> float:
    """Useful model FLOPs of a window: each prompt of ``plen`` real tokens
    (causal, so position i attends over i + 1), one head row per prefill,
    and every decode token at its context length with one head row.
    Padding and idle rows are not useful work and are left out."""
    total = 0.0
    for plen in prompt_lens:
        # sum over positions of the causal context = plen (plen + 1) / 2
        mean_ctx = (plen + 1) / 2.0
        total += plen * token_flops(d, mean_ctx) + head_flops(d)
    for ctx in decode_contexts:
        total += token_flops(d, ctx) + head_flops(d)
    return total
