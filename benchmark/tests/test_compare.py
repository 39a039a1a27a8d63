"""The comparison that decides `correct`, and its control, at a size a
test run can hold: a drive tree laid out by the reference is correct;
the reference with one guarantee broken is not."""

import numpy as np
import pytest

from benchmark import compare, faults

CFG = {"drives": 6, "data_shards": 4, "parity_shards": 2,
       "erasure_block_bytes": 1 << 20, "write_quorum": 4}


class FakeServer:
    def __init__(self, root):
        self.drive_root = str(root)


class FakeBodies:
    def __init__(self, body):
        self._body = body

    def body(self, key):
        return self._body


@pytest.fixture()
def tree(tmp_path):
    body = np.random.default_rng(11).bytes(2 << 20)
    files = compare.reference_shard_files(body, 4, 2, 1 << 20)
    for d, data in enumerate(files, start=1):
        # shard i on drive (i + 2) % 6: placement is not assumed
        p = tmp_path / f"d{(d + 2) % 6 + 1}" / "bench" / "w00" / "000001" \
            / "uuid"
        p.mkdir(parents=True)
        (p / "part.1").write_bytes(data)
    return FakeServer(tmp_path), body


def check(srv, body):
    return compare.check_object_on_disk(srv.drive_root, CFG, "bench",
                                        "w00/000001", body)


def test_the_reference_layout_is_correct(tree):
    srv, body = tree
    files = compare.reference_shard_files(body, 4, 2, 1 << 20)
    assert [len(f) for f in files] == [2 * (32 + (1 << 18))] * 6
    assert check(srv, body) == {"right": 6, "wrong": 0}


def test_control_parity_that_is_not_upstreams(tree):
    srv, body = tree
    faults.wrong_matrix(srv, CFG, ["w00/000001"], FakeBodies(body))
    assert check(srv, body) == {"right": 4, "wrong": 2}


def test_control_one_drive_short_of_the_write_quorum(tree):
    srv, body = tree
    faults.below_quorum(srv, CFG, ["w00/000001"], FakeBodies(body))
    got = check(srv, body)
    assert got["right"] == CFG["write_quorum"] - 1 and got["wrong"] == 0


def test_one_bit_on_one_drive_is_a_wrong_shard(tree):
    srv, body = tree
    path = next(iter(compare.shard_files_on_disk(
        srv.drive_root, 6, "bench", "w00/000001").values()))
    data = bytearray(open(path, "rb").read())
    data[40] ^= 1
    open(path, "wb").write(data)
    assert check(srv, body) == {"right": 5, "wrong": 1}


def test_the_same_shard_twice_counts_once(tree):
    srv, body = tree
    paths = compare.shard_files_on_disk(srv.drive_root, 6, "bench",
                                        "w00/000001")
    first, second = list(paths.values())[:2]
    open(second, "wb").write(open(first, "rb").read())
    assert check(srv, body) == {"right": 5, "wrong": 1}


def test_listing_diff_counts_missing_extra_and_changed():
    want = {"a": ("e1", 10), "b": ("e2", 10), "c": ("e3", 10)}
    assert compare.listing_diff(dict(want), want) == 0
    got = {"a": ("e1", 10), "b": ("XX", 10), "d": ("e4", 10)}
    assert compare.listing_diff(got, want) == 3
