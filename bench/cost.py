"""What a scan needs to move, from the query spec alone.

A snapshot scan of one key reads every version slot of the key's page:
its commit timestamp, its codec tag and its aggregable field, one int32
each.  With the mirror's 8 version slots that is 8 x 3 x 4 = 96 bytes per
key, plus the snapshot's member array (one int32 per member above the
floor).  The count depends on the plan and the snapshot only, never on
which kernel mode (scalar, flat, chunked) or how many passes serve it, so
a roofline share built on it reads the same work whatever implements it.
"""

from __future__ import annotations

import json
from pathlib import Path

SLOT_FIELDS = 3          # ts, tag, field
WORD_BYTES = 4           # int32
PEAKS = Path(__file__).resolve().parent / "peaks.json"

# The fused scan kernels (`rss_scan_agg` family: the flat grouped kernel,
# which also serves the scalar aggregate, and the chunked kernel's two
# stages) in the chip's trace: Pallas custom calls named after the jitted
# op that launches them (`trace_reduce.op_label`, e.g.
# "rss_scan_agg_grouped:custom-call").  The view delta fold
# ("rss_delta_fold:custom-call") is not a scan and is left out.
def is_scan_kernel(label: str) -> bool:
    return label.startswith("rss_scan_agg") and \
        label.endswith(":custom-call")


def spec_keys(spec: tuple) -> int:
    """Keys a spec reads (a key in two groups is read twice)."""
    if spec[0] == "group":
        return sum(len(g) for g in spec[1])
    return len(spec[1])


def scan_bytes(spec: tuple, n_members: int, *, slots: int = 8) -> int:
    """Bytes a snapshot scan of `spec` needs to read."""
    return (spec_keys(spec) * slots * SLOT_FIELDS * WORD_BYTES
            + n_members * WORD_BYTES)


def peaks(device_kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]
