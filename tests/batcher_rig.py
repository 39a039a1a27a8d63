"""A stripe batcher whose device function and staging copy stop where a
test tells them to: the rig of the pipelined-dispatch cases in
tests/test_stripe_batcher.py and tests/test_batcher_mesh.py. Order is
established with events only, never with the clock (ROADMAP C8): a wait
has a timeout so that a broken batcher fails the test instead of
hanging it, and nothing is asserted about how long anything took."""

import functools
import threading
import time

import numpy as np

from minio_tpu.io.bufpool import BufferPool
from minio_tpu.object.erasure_object import (_get_split, _host_apply_rows,
                                             _host_deframe, _host_rows)
from minio_tpu.ops import device, gf256
from minio_tpu.ops.batcher import StripeBatcher, _Pending
from minio_tpu.storage import bitrot

K, M, SHARD = 8, 4, 1024
WAIT_S = 60.0          # a timeout, not an expectation

ROUTES = ("put", "split")
# The routes that ship, each with its own host function and demux: what
# a staged dispatch's rows are held to (the kept staging buffer).
SHIPPED = ("put", "get", "reconstruct")
# A trailing shape at which a 64-block dispatch is just over 64 MiB,
# the pool's largest class until PR 35: route get's own frame at EC 8+4.
BIG_SHARD = 131072 + 32
# Survivors and losses of the `reconstruct` route: data shards 1 and 4
# are gone, parity 8 and 9 stand in.
USE, LOST = (0, 2, 3, 5, 6, 7, 8, 9), (1, 4)


def _xor(stacked):
    return stacked ^ np.uint8(0x5A)


def window(blocks, seed, shard=SHARD, route="put"):
    """One member's window in the form its route stacks: [B, K, shard]
    bytes; on route `get` each piece is a bitrot frame, its digest the
    right one but for one turned byte in block 0 (a verdict of each
    kind)."""
    rng = np.random.default_rng(seed)
    out = rng.integers(0, 256, size=(blocks, K, shard), dtype=np.uint8)
    if route == "get":
        out[:, :, :32] = bitrot.hash_blocks_many(
            bitrot.DEFAULT_ALGORITHM,
            out[:, :, 32:].reshape(blocks * K, shard - 32)) \
            .reshape(blocks, K, 32)
        out[0, 1, 40] ^= 0x01
    return out


def same(route, got, want):
    """`got` is byte for byte what `want` is, in the route's own form."""
    if route in ("split", "reconstruct"):
        assert np.array_equal(got, want)
        return
    if route == "get":
        assert np.array_equal(got[0], want[0]) and not got[0].all()
        assert np.array_equal(got[1], want[1])
        return
    assert len(got) == len(want)
    for dg, dw in zip(got, want):
        assert len(dg) == len(dw)
        for (hg, bg), (hw, bw) in zip(dg, dw):
            assert np.array_equal(np.asarray(hg), np.asarray(hw))
            assert np.array_equal(np.asarray(bg), np.asarray(bw))


def wait(event, what):
    assert event.wait(WAIT_S), f"never happened: {what}"


def until(cond, what):
    end = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < end, f"never happened: {what}"
        time.sleep(0.002)


class Gate:
    """`fn` with a door on each call: call i says `entered[i]`, waits
    for `go[i]` if i is in `hold` — before running `fn`, or after it
    with `after` — and says `left[i]` on its way out. The calls of one
    gate come one at a time (the lane and the dispatcher are one thread
    each), so a plain counter numbers them."""

    def __init__(self, fn, hold=(), after=False, fail=()):
        self.fn = fn
        self.hold, self.after, self.fail = set(hold), after, set(fail)
        self.calls = 0
        self.entered = [threading.Event() for _ in range(8)]
        self.go = [threading.Event() for _ in range(8)]
        self.left = [threading.Event() for _ in range(8)]
        self.on_call = None

    def __call__(self, *args):
        i = self.calls
        self.calls += 1
        self.entered[i].set()
        try:
            if self.on_call is not None:
                self.on_call(i)
            if i in self.hold and not self.after:
                wait(self.go[i], f"go[{i}]")
            if i in self.fail:
                raise RuntimeError(f"device fell over in call {i}")
            out = self.fn(*args)
            if i in self.hold and self.after:
                wait(self.go[i], f"go[{i}]")
            return out
        finally:
            self.left[i].set()


class Member:
    """One frame() call on a thread of its own."""

    def __init__(self, sb, stacked):
        self.stacked = stacked
        self.rows = None
        self.exc = None
        self.thread = threading.Thread(target=self._run, args=(sb,))
        self.thread.start()

    def _run(self, sb):
        try:
            self.rows = sb.frame(self.stacked)
        except BaseException as e:  # noqa: BLE001 - the tests assert it
            self.exc = e

    def returned(self):
        self.thread.join(WAIT_S)
        assert not self.thread.is_alive(), "member still inside frame()"
        return self

    def inside(self):
        return self.thread.is_alive()


class Rig:
    """A pinned batcher on route `put` (the per-drive rows contract) or
    on a `split_fn` route, behind gates: `dev` holds device calls
    before they run, `stage` holds the dispatcher after a batch's copy.
    A batch is two members of half the fill target each, so the
    dispatcher takes it the moment the second arrives and never on a
    timer. `device_fn` puts a real framer behind the device gate (the
    host function stays the reference)."""

    def __init__(self, route, hold_dev=(), hold_stage=(), fail=(),
                 device_fn=None, dev_after=False, shard=SHARD):
        slices = {"route": "reconstruct",
                  "split_fn": lambda out, off, c, _m: out[off:off + c]}
        if route == "split":
            fn, kw = _xor, slices
        elif route == "reconstruct":
            rows = np.ascontiguousarray(
                gf256.decode_matrix(K, M, USE)[list(LOST), :])
            fn, kw = functools.partial(_host_apply_rows, rows), slices
        elif route == "get":
            fn, kw = _host_deframe, {"route": "get", "split_fn": _get_split}
            # the device de-framer answers with the verdicts alone
            device_fn = device_fn or (lambda s: _host_deframe(s)[0])
        else:
            fn, kw = (lambda s: _host_rows(K, M, s)), {"route": "put"}
        self.fn, self.route, self.shard = fn, route, shard
        # What each device call was handed: (rows in the batch, rows the
        # batcher called real, whether any padding row held a set bit).
        self.seen = []

        def seeing(stacked):
            real = getattr(device._batch, "real", None)
            self.seen.append((stacked.shape[0], real,
                              bool(stacked[real:].any())))
            return (device_fn or fn)(stacked)
        self.dev = Gate(seeing, hold=hold_dev, fail=fail, after=dev_after)
        self.dev.mesh_devices = getattr(device_fn, "mesh_devices", 1)
        self.pool = BufferPool(max_per_class=4)
        self.leases, self.outstanding = [], []
        lease = self.pool.lease

        def counted(size):
            got = lease(size)
            self.leases.append(got)
            self.outstanding.append(self.pool.stats()["outstanding"])
            return got
        self.pool.lease = counted
        self.sb = StripeBatcher(self.dev, fn, probe_fn=lambda: True,
                                min_device_blocks=8, max_wait_s=WAIT_S,
                                pool=self.pool, **kw)
        self.sb.force(True)
        self.stage = Gate(self.sb._stage, hold=hold_stage, after=True)
        self.sb._stage = self.stage
        self.half = self.sb._fill_target() // 2
        self.members = []
        with self.sb._mu:
            self.sb._inflight += 1       # nobody sees itself solo

    def send(self, seed, blocks=None, n=2):
        """n members of one batch, on their threads."""
        got = [Member(self.sb, window(blocks or self.half, seed + i,
                                      self.shard, self.route))
               for i in range(n)]
        self.members += got
        return got

    def send_in_order(self, seed, counts):
        """One batch of members of `counts` blocks, queued in that
        order: all but the last stay under the fill target together,
        so the dispatcher takes the batch when the last arrives."""
        assert sum(counts[:-1]) < self.sb._fill_target() <= sum(counts)
        got = []
        for i, c in enumerate(counts):
            got += self.send(seed + i, blocks=c, n=1)
            if i < len(counts) - 1:
                until(lambda: len(self.sb._pending) == i + 1,
                      f"member {i} queued")
        return got

    def synchronous(self, members):
        """The same windows through the one-thread path, on a batcher
        with no gate: what every pipelined result is held to."""
        ref = StripeBatcher(self.fn, self.fn, probe_fn=lambda: True,
                            min_device_blocks=8, route=self.sb.route,
                            split_fn=self.sb._split_fn)
        ref.force(True)
        pend = [_Pending(m.stacked, None) for m in members]
        ref._run_batch(pend)
        assert all(p.exc is None for p in pend)
        return [p.rows for p in pend]

    def end(self):
        """Every door open, every thread home, the lane free again."""
        for g in (self.dev, self.stage):
            for e in g.go:
                e.set()
        with self.sb._mu:
            self.sb._inflight -= 1
        for m in self.members:
            m.thread.join(WAIT_S)
        self.sb.close()
        assert not any(m.inside() for m in self.members)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False
