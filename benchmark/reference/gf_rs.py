"""Plain Reed-Solomon over GF(2^8), as upstream MinIO encodes objects.

Written from the public description of klauspost/reedsolomon (the
library upstream links): field polynomial x^8+x^4+x^3+x^2+1 (0x11D),
generator 2; the coding matrix is the (k+m) x k Vandermonde matrix
V[r][c] = r^c multiplied by the inverse of its top k x k square, so the
first k rows are the identity (data shards are stored as they are) and
rows k.. give the parity. Straightforward numpy, no code shared with
`minio_tpu/`.
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[0:255]
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def power(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * n) % 255])


def matmul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for t, v in enumerate(row):
                acc ^= mul(v, b[t][j])
            out[i][j] = acc
    return out


def invert(m: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan over GF(2^8)."""
    n = len(m)
    a = [list(row) + [1 if i == j else 0 for j in range(n)]
         for i, row in enumerate(m)]
    for col in range(n):
        piv = next(r for r in range(col, n) if a[r][col])
        a[col], a[piv] = a[piv], a[col]
        s = inv(a[col][col])
        a[col] = [mul(v, s) for v in a[col]]
        for r in range(n):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [v ^ mul(f, w) for v, w in zip(a[r], a[col])]
    return [row[n:] for row in a]


def vandermonde(k: int, m: int) -> list[list[int]]:
    return [[power(r, c) for c in range(k)] for r in range(k + m)]


def coding_matrix(k: int, m: int) -> list[list[int]]:
    """(k+m) x k, identity on top: upstream's matrix."""
    vm = vandermonde(k, m)
    return matmul(vm, invert(vm[:k]))


def raw_vandermonde_parity(k: int, m: int) -> list[list[int]]:
    """The parity rows of the Vandermonde matrix NOT made systematic:
    a code that still recovers its own data but is not upstream's. Only
    the control uses it."""
    return vandermonde(k, m)[k:]


def mul_const(c: int, data: np.ndarray) -> np.ndarray:
    """c * data, bytewise, by one 256-entry table."""
    table = np.array([mul(c, x) for x in range(256)], dtype=np.uint8)
    return table[data]


def encode(data: np.ndarray, k: int, m: int,
           parity_rows: list[list[int]] | None = None) -> np.ndarray:
    """data: uint8 [k, n] -> parity uint8 [m, n]."""
    rows = parity_rows if parity_rows is not None \
        else coding_matrix(k, m)[k:]
    out = np.zeros((m, data.shape[1]), dtype=np.uint8)
    for r, row in enumerate(rows):
        for c, coef in enumerate(row):
            if coef:
                out[r] ^= mul_const(coef, data[c])
    return out
