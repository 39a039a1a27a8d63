"""The key rules of the traffic generator, under interleavings chosen
to hurt: whatever order the workers' operations land in, every answer
has one right value."""

import json
import os
import random

import pytest

from benchmark import traffic

MIX = {"size": 4096, "workers": 5, "cycle": {"GET": 9, "STAT": 6, "PUT": 3,
                                              "DELETE": 2},
       "preload": 4, "bodies": 3}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 987654321012])
def test_cycles_hold_the_mix_exactly_and_never_delete_from_nothing(seed):
    sched = traffic.Schedule(MIX, seed, worker=3)
    live, counts = 0, {}
    for i in range(20 * 50):
        op = sched.next_op(live)
        assert not (op == "DELETE" and live == 0)
        live += {"PUT": 1, "DELETE": -1}.get(op, 0)
        counts[op] = counts.get(op, 0) + 1
    assert counts == {"GET": 450, "STAT": 300, "PUT": 150, "DELETE": 100}


def test_seeds_send_the_same_work_in_another_order():
    a = traffic.cycle_ops(MIX, 1, 0, 0)
    b = traffic.cycle_ops(MIX, 2, 0, 0)
    assert sorted(a) == sorted(b) and a != b
    assert traffic.cycle_ops(MIX, 1, 0, 0) == a


def test_a_mix_that_only_deletes_after_puts_still_runs():
    mix = {**MIX, "cycle": {"PUT": 1, "DELETE": 1}}
    sched = traffic.Schedule(mix, 5, 0)
    live = 0
    for _ in range(200):
        op = sched.next_op(live)
        assert not (op == "DELETE" and live == 0)
        live += 1 if op == "PUT" else -1


class ModelStore:
    """What the configuration guarantees, as a dict: read-after-write,
    404 after delete."""

    def __init__(self):
        self.objects = {}

    def apply(self, op, key, body):
        if op == "PUT":
            self.objects[key] = body
            return 200, None
        if op in ("GET", "STAT"):
            return (200, self.objects[key]) if key in self.objects \
                else (404, None)
        if op == "DELETE":
            self.objects.pop(key, None)
            return 204, None
        raise ValueError(op)


@pytest.mark.parametrize("order_seed", range(8))
def test_every_answer_is_decidable_under_any_interleaving(order_seed):
    """Five workers against one model store, their operations
    interleaved at random — and adversarially: a worker that has just
    been acknowledged a PUT is made to wait while the others run. Each
    answer is judged by the worker's own rule alone."""
    seed = 99
    bodies = traffic.Bodies(seed, MIX["size"], MIX["bodies"])
    store = ModelStore()
    for i in range(MIX["preload"]):
        store.apply("PUT", traffic.pre_key(i), bodies.body(traffic.pre_key(i)))
    order = random.Random(order_seed)
    workers = []
    for wid in range(MIX["workers"]):
        workers.append({"wid": wid, "live": [], "n": 0,
                        "sched": traffic.Schedule(MIX, seed, wid),
                        "rng": random.Random(f"{seed}/{wid}/keys")})
    seen_keys = set()
    for _ in range(3000):
        w = order.choice(workers[:2] if order.random() < 0.3 else workers)
        op = w["sched"].next_op(len(w["live"]))
        if op == "PUT":
            key = traffic.own_key(w["wid"], w["n"])
            w["n"] += 1
            assert key not in seen_keys          # never written twice
            seen_keys.add(key)
        elif op == "DELETE":
            key = w["live"].pop(w["rng"].randrange(len(w["live"])))
        else:
            i = w["rng"].randrange(MIX["preload"] + len(w["live"]))
            key = traffic.pre_key(i) if i < MIX["preload"] \
                else w["live"][i - MIX["preload"]]
        status, body = store.apply(op, key, bodies.body(key))
        if op == "PUT":
            w["live"].append(key)
        if op in ("GET", "STAT"):
            assert status == 200 and body == bodies.body(key)
    live = {k for w in workers for k in w["live"]}
    assert set(store.objects) == live | {traffic.pre_key(i)
                                         for i in range(MIX["preload"])}


def test_bodies_differ_and_their_hashes_are_the_plain_ones():
    import hashlib
    bodies = traffic.Bodies(2**31 + 11, 4096, 2)
    a, b = traffic.own_key(0, 0), traffic.own_key(0, 2)   # same variant
    assert bodies.body(a) != bodies.body(b)
    assert bodies.body(a)[:-32] == bodies.body(b)[:-32]
    head, st, sha, etag = bodies.parts(a)
    assert sha == hashlib.sha256(bodies.body(a)).hexdigest()
    assert etag == hashlib.md5(bodies.body(a)).hexdigest()
    again = traffic.Bodies(2**31 + 11, 4096, 2)
    assert again.body(a) == bodies.body(a)


def test_every_mix_file_loads():
    here = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")
    names = [f[:-5] for f in os.listdir(here) if f.endswith(".json")]
    assert names
    for name in names:
        mix = traffic.load_mix(name)
        assert mix["workers"] >= 1 and mix["size"] % (1 << 20) == 0
        json.dumps(mix)
