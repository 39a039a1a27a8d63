"""`kernel.reconstruct_roofline`: the generic roofline share
(`readers.device_trace`) of a kind of work that is this metric's own.

`rebuild_work`, per erasure block of B bytes of which `lost` of the k
data pieces are gone: the GF(2^8) rebuild that route `reconstruct`
brings to the chip, and nothing else — read k surviving pieces (B
bytes, un-framed: the host has verified them and cut their digests
off), write the rebuilt ones (lost * B / k); multiply-accumulates: k a
rebuilt byte, lost * B / k of them -> lost * B. No hash is counted:
the survivors' bitrot verify runs on the host in today's program
(PERF.md section 6, PR 34), and the day it moves to the chip it is
another kernel's work, with a metric of its own, in this metric's
denominator and not in its numerator. The kind is entered into
`work.WORK` from here: a kind of work belongs to the metric that
reads it.

`lost` is the mean number of DATA shards a GET of the window had on
the configuration's dead drives, so it need not be whole. It comes
from the clients' own log (which keys they read, each weighed by its
payload inside the window, as the rate is) and the placement rule
(`reference/layout.py`); nothing of it is the program's. A shard that
is there and rotten (the mix's `rotten`) is not counted: one window of
one object. A configuration without dead drives, or a window without
a GET, reports nothing."""

from benchmark import readers, work
from benchmark.reference import layout
from benchmark.traffic import BUCKET


def rebuild_work(k: int, m: int, block: int, blocks: float,
                 lost: float) -> dict:
    del m                        # any k survivors do, parity or not
    return {"bytes": blocks * (block + lost * block / k),
            "ops": blocks * lost * block}


def mean_lost(inside: dict, cfg: dict):
    """`inside`: {key: payload bytes of its GETs inside the window}."""
    weight = sum(inside.values())
    if not weight:
        return None
    return sum(w * layout.lost_data_shards(
        BUCKET, key, cfg["drives"], cfg["data_shards"], cfg["dead_drives"])
        for key, w in inside.items()) / weight


def read(ctx, spec):
    cfg = ctx["config"]
    if not cfg.get("dead_drives"):
        return None
    lost = mean_lost(ctx.get("payload_by_key", {}).get("GET", {}), cfg)
    if not lost:
        return None
    work.WORK[spec["work"]] = \
        lambda k, m, block, blocks: rebuild_work(k, m, block, blocks, lost)
    share = readers.device_trace(ctx, spec)
    if share is not None:
        ctx["notes"][spec["work"] + "_roofline"]["lost"] = lost
    return share
