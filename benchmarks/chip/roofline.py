"""Peaks of the chip and the logical bytes of the fire kernel's calls.

The bytes are counted per call from the fabric's own sizes, not from
the kernel's operand shapes, so that any implementation of the same
work is held to the same count: a kernel that runs several blocks per
call or packs its registers changes its time, not this yardstick.
"""
from __future__ import annotations

import json
from pathlib import Path

WORD = 4          # int32 tokens and registers


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind that is not in
    ``peaks.json`` is an error, never a default."""
    table = json.loads((Path(__file__).with_name("peaks.json"))
                       .read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"in peaks.json (have {sorted(table)})")
    return table[device_kind]


def fire_block_bytes(arcs: int, inputs: int, outputs: int, slots: int,
                     cycles: int) -> int:
    """Logical bytes one ``dataflow_fire_block`` call must move for
    ``slots`` slots over ``cycles`` cycles, per slot:

    - the arc registers, full bit and value, read and written once:
      2 words x 2 x arcs;
    - the feed tokens the call's cycles can consume: inputs x cycles,
      and each input's stream length (read) and pointer (read and
      written): 3 x inputs;
    - the output accumulators, last value and count, read and written:
      2 x 2 x outputs;
    - the slot's clock gate (read), its firings and its last busy cycle
      (written): 3 words."""
    per_slot = (4 * arcs + inputs * cycles + 3 * inputs + 4 * outputs + 3)
    return WORD * slots * per_slot
