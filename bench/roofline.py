"""The chip's published peaks and the arithmetic that scores work
against them."""
from __future__ import annotations

from common import load_json

#: Bytes of one operand or result element at the configurations' 16-bit
#: LNS word.
WORD_BYTES = 2


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind the table lacks is an error."""
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


def mac_least_s(calls, pk: dict) -> tuple:
    """The least time the chip could take for ⊞-MAC launches (M, K, N):
    per launch the larger of 2·M·K·N operations at the bf16 peak (the
    chip's fastest published rate for a 16-bit multiply-accumulate) and
    the operands and result at the 16-bit word over HBM bandwidth.
    Returns (seconds, which term bounds most of them)."""
    total, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for m, k, n in calls:
        c = 2.0 * m * k * n / pk["bf16_flops"]
        b = WORD_BYTES * (m * k + k * n + m * n) / pk["hbm_bytes_per_s"]
        total += max(c, b)
        by["compute" if c >= b else "memory"] += max(c, b)
    return total, max(by, key=by.get)


def mfu(model_ops_per_step: float, steps: int, window_s: float, chips: int,
        pk: dict) -> float:
    """The step's model operations per second over chips × the bf16 peak,
    in percent."""
    return 100.0 * model_ops_per_step * steps / window_s / (
        chips * pk["bf16_flops"])
