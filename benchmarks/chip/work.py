"""The work a device stage must do, computed from its schema."""
from __future__ import annotations

FIELD_BYTES = {"i4": 4, "f4": 4, "i8": 8, "f8": 8}


def device_stage_bytes(rows: int, codes_in, codes_out=None) -> int:
    """Bytes a device stage reads and writes for ``rows`` rows: every
    column in once and every column out once.  Padding rows and copies
    the implementation adds are not the stage's work and are not
    counted."""
    codes_out = codes_in if codes_out is None else codes_out
    per_row = sum(FIELD_BYTES[c] for c in codes_in) + sum(
        FIELD_BYTES[c] for c in codes_out)
    return int(rows) * per_row
