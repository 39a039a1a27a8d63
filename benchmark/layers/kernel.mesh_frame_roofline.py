"""`kernel.mesh_frame_roofline`: the framer's share of its roofline
where a mesh of chips frames every batch.

`P("stripe")` cuts a batch evenly, by erasure block, over the chips,
so a chip's share of the window's work is the whole (the blocks the
clients' PUTs brought to the device, as `readers.device_trace` counts
them for `kernel.frame_roofline`) divided by the chips in the trace;
`work.least_seconds` is linear in the blocks, so the least time of a
chip's share is the whole's over the chips. `trace.reduce` has already
averaged the programs' device seconds over the planes, so the
denominator `readers.device_trace` takes is the mean device time per
second of a chip. Hence: its `roofline_share` over the chips. The
least time counts real blocks only and the device time includes what
the chips spent on bucket padding: the share cannot pass 100 %.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import readers  # noqa: E402


def read(ctx, spec):
    whole = readers.device_trace(ctx, {**spec, "quantity": "roofline_share"})
    if whole is None:
        return None
    chips = ctx["trace"]["chips"]
    note = ctx["notes"].pop(spec["work"] + "_roofline")
    ctx["notes"]["mesh_" + spec["work"] + "_roofline"] = {
        "chips": chips, "bound": note["bound"],
        "blocks_per_s_a_chip": note["blocks_per_s"] / chips,
        "least_s_per_s_a_chip": note["least_s_per_s"] / chips,
        "device_s_per_s_a_chip": note["device_s_per_s"],
        "traced_s": note["traced_s"],
        "programs_run_a_chip": note["programs_run"]}
    return whole / chips
