"""The plain reference that decides ``correct``, and the comparison.

Sinkhorn-WMD of one query against many documents, as the paper's Fig. 3
writes it (Tithi & Petrini, arXiv 2107.06433), one document at a time:

    K = exp(-lambda M), M[i, j] = |vec(query word i) - vec(doc word j)|
    x = 1 / m (m real query words)
    repeat max_iter times:  u = 1/x;  v = c / (K^T u);  x = diag(1/r) K v
    u = 1/x;  v = c / (K^T u);  WMD = sum(u * ((K * M) v))

It imports nothing of the program. The cost is the plain difference norm,
never the ``|a|^2 + |b|^2 - 2 a.b`` expansion, so a word shared by query
and document costs exactly 0; every reduction is an elementwise product and
a sum, and runs under ``highest`` matmul precision in case the compiler
turns one into a matrix-unit contraction. It runs in
blocks of documents on the default device, after the program's state is
freed. ``dtype=bfloat16`` gives the control: the same reference a precision
step below the configuration's float32.
"""
from __future__ import annotations

import functools

import numpy as np

COST_CHUNK = 2048            # vocabulary rows per cost block


@functools.lru_cache(maxsize=None)
def _programs(lamb: float, iters: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def cost_rows(qvecs, vecs):
        """(v_r, w), (V, w) -> K^T, (K*M)^T with a zero row appended for
        the padding word id V."""
        v, w = vecs.shape
        pad = -v % COST_CHUNK
        blocks = jnp.pad(vecs, ((0, pad), (0, 0))).reshape(-1, COST_CHUNK, w)

        def one(blk):
            d = qvecs[:, None, :] - blk[None, :, :]
            return jnp.sqrt(jnp.sum(d * d, axis=-1))

        m = jax.lax.map(one, blocks)                      # (nb, v_r, chunk)
        m = jnp.moveaxis(m, 1, 0).reshape(qvecs.shape[0], -1)[:, :v]
        k = jnp.exp(-lamb * m)
        zero = jnp.zeros((1, qvecs.shape[0]), k.dtype)
        return (jnp.concatenate([k.T, zero]),
                jnp.concatenate([(k * m).T, zero]))

    @jax.jit
    def solve_block(kt, kmt, r, mask, cols, wts):
        g = kt[cols]                                      # (B, L, v_r)
        real = mask > 0
        x = jnp.broadcast_to(1.0 / jnp.sum(mask), (cols.shape[0], r.size))
        x = x.astype(g.dtype)

        def sweep(x):
            u = jnp.where(real, 1.0 / x, 0.0)
            ktu = jnp.sum(g * u[:, None, :], axis=-1)     # (B, L)
            v = jnp.where(wts > 0, wts / jnp.where(wts > 0, ktu, 1.0), 0.0)
            return u, v

        def body(_, x):
            _, v = sweep(x)
            return jnp.sum(g * v[:, :, None], axis=1) / r[None, :]

        x = jax.lax.fori_loop(0, iters, body, x)
        u, v = sweep(x)
        km = jnp.sum(kmt[cols] * v[:, :, None], axis=1)  # (B, v_r)
        return jnp.sum(u * km, axis=-1)

    return cost_rows, solve_block


def distances(vecs, q_ids, q_w, cols, counts, *, lamb: float, iters: int,
              v_r: int, dtype=None, block: int = 8192) -> np.ndarray:
    """Reference distances of one query against documents.

    ``vecs`` (V, w) embeddings (a device array may be passed to skip the
    upload); ``q_ids``/``q_w`` the query's words and frequencies (ids < 0
    are padding); ``cols``/``counts`` (N, L) the documents' word ids (pad
    id V) and raw counts (pad 0), normalised here. Returns (N,) float64."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    v = vecs.shape[0]
    keep = np.asarray(q_ids) >= 0
    ids = np.zeros(v_r, np.int32)
    ids[:keep.sum()] = np.asarray(q_ids)[keep]
    r = np.ones(v_r, np.float64)
    r[:keep.sum()] = np.asarray(q_w, np.float64)[keep]
    mask = np.zeros(v_r, np.float32)
    mask[:keep.sum()] = 1.0
    cost_rows, solve_block = _programs(float(lamb), int(iters))
    with jax.default_matmul_precision("highest"):
        return _distances(cost_rows, solve_block, vecs, ids, r, mask, cols,
                          counts, dtype, block)


def _distances(cost_rows, solve_block, vecs, ids, r, mask, cols, counts,
               dtype, block):
    import jax.numpy as jnp
    v = vecs.shape[0]
    vd = jnp.asarray(vecs, dtype)
    kt, kmt = cost_rows(vd[ids], vd)
    kt = kt * jnp.asarray(mask, dtype)[None, :]
    kmt = kmt * jnp.asarray(mask, dtype)[None, :]
    counts = np.asarray(counts, np.float64)
    tot = counts.sum(axis=1, keepdims=True)
    wts = counts / np.where(tot > 0, tot, 1.0)
    n = cols.shape[0]
    b = min(block, -(-n // 512) * 512)
    out = np.empty(n, np.float64)
    for lo in range(0, n, b):
        c = np.asarray(cols[lo:lo + b], np.int32)
        w = wts[lo:lo + b]
        m = c.shape[0]
        if m < b:
            c = np.pad(c, ((0, b - m), (0, 0)), constant_values=v)
            w = np.pad(w, ((0, b - m), (0, 0)))
        d = solve_block(kt, kmt, jnp.asarray(r, dtype),
                        jnp.asarray(mask, dtype), jnp.asarray(c),
                        jnp.asarray(w, dtype))
        out[lo:lo + m] = np.asarray(d, np.float64)[:m]
    return out


# -- the comparison -----------------------------------------------------------

def rows_error(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest relative gap of served distances from the reference."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-12)))


def topk_errors(ids: np.ndarray, dists: np.ndarray, ref: np.ndarray,
                slack: float) -> tuple[float, int]:
    """(relative gap of each served distance from the reference's distance
    of the same document, true neighbours left out).

    A document is a neighbour left out when it is not served and the
    reference puts it below the served set's worst document by more than
    ``slack`` (relative): a bound tier that drops a true neighbour shows
    here, and a swap within rounding of the k-th distance does not."""
    ids = np.asarray(ids, np.int64)
    if ids.size == 0 or ids.min() < 0 or ids.max() >= ref.size \
            or np.unique(ids).size != ids.size:
        return float("inf"), int(ref.size)
    err = rows_error(dists, ref[ids])
    worst = float(ref[ids].max())
    below = ref < worst * (1.0 - slack)
    below[ids] = False
    return err, int(below.sum())
