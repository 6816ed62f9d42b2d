"""The work a step and a flush must do, from their shapes, and the chip's
published peaks (``chipbench/peaks.json``, keyed by ``device_kind``)."""

from __future__ import annotations

import json
from pathlib import Path

F32 = 4  # bytes
I32 = 4

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a kind not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        known = sorted(k for k in table if k != "source")
        raise KeyError(f"no published peaks for device kind {device_kind!r}; known: {known}")
    return table[device_kind]


def state_cols(config: dict) -> int:
    """Logical width of the packed per-feature state: (w, psi) for the
    cache-based solvers, (w, z, n) for FTRL."""
    return 3 if config["solver"] == "ftrl" else 2


def step_bytes(batch: int, p_max: int, cols: int) -> int:
    """The least bytes one lazy step must move: the gathered state rows
    read and written back at their logical width, plus the batch's ids,
    values and labels."""
    rows = batch * p_max
    return 2 * rows * cols * F32 + rows * (I32 + F32) + batch * F32


def flush_bytes(dim: int, cols: int) -> int:
    """One pass over the whole ``[dim, cols]`` state: read plus write."""
    return 2 * dim * cols * F32
