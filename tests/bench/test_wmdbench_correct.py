"""The harness's ``correct`` on the CPU at a tiny size: the reference is
the paper's algorithm, sound runs of every cell of the tiny checkout pass,
and the control and every fault a cell can have (an answer altered where
it is produced, half of a batch left out, a true neighbour dropped) come
out not correct."""
import numpy as np
import pytest

from wmdbench_testing import tiny_cells, tiny_root

from wmdbench import cell, reference, report, spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("tiny")))


def _run(root, name, seconds=0.3, answer=None):
    import jax
    import time
    bm = spec.load_benchmark(root)
    r = cell.run(bm, name, seed=2**31 + 17, seconds=seconds, trace=False,
                 devices=jax.devices(), t_start=time.perf_counter(),
                 answer=answer, root=root)
    line = report.result_line(bm, name, r, False, jax.devices(), root)
    assert list(line)[-1] == "checks"
    assert line["metrics"]["setup_s"]["value"] > 0
    return line


def _dense_fig3(vecs, q_ids, q_w, cols, counts, lamb, iters):
    """The paper's Fig. 3 in float64 numpy, with a dense (V, N) c."""
    v, n = vecs.shape[0], cols.shape[0]
    c = np.zeros((v, n))
    for j in range(n):
        live = counts[j] > 0
        c[cols[j][live], j] = counts[j][live] / counts[j][live].sum()
    a = vecs[q_ids].astype(np.float64)
    m = np.sqrt(((a[:, None, :] - vecs[None].astype(np.float64)) ** 2)
                .sum(-1))
    k = np.exp(-lamb * m)
    x = np.full((q_ids.size, n), 1.0 / q_ids.size)
    for _ in range(iters):
        u = 1.0 / x
        vv = np.where(c > 0, c / np.where(c > 0, k.T @ u, 1.0), 0.0)
        x = (k / q_w[:, None]) @ vv
    u = 1.0 / x
    vv = np.where(c > 0, c / np.where(c > 0, k.T @ u, 1.0), 0.0)
    return (u * ((k * m) @ vv)).sum(0)


def test_reference_is_the_papers_algorithm():
    rng = np.random.default_rng(0)
    v, n = 300, 40
    vecs = (rng.standard_normal((v, 16)) * 1.3).astype(np.float32)
    cols = np.full((n, 8), v, np.int32)
    counts = np.zeros((n, 8), np.float32)
    for j in range(n):
        k = rng.integers(2, 9)
        cols[j, :k] = rng.choice(v, k, replace=False)
        counts[j, :k] = rng.integers(1, 4, k)
    q_ids = rng.choice(v, 5, replace=False)
    q_w = rng.integers(1, 4, 5).astype(np.float64)
    q_w /= q_w.sum()
    got = reference.distances(vecs, np.concatenate([q_ids, [-1, -1]]),
                              np.concatenate([q_w, [0, 0]]), cols, counts,
                              lamb=1.0, iters=7, v_r=8, block=16)
    want = _dense_fig3(vecs, q_ids, q_w, cols, counts, 1.0, 7)
    np.testing.assert_allclose(got, want, rtol=2e-5)


@pytest.mark.parametrize("name", tiny_cells())
def test_sound_runs_are_correct(root, name):
    line = _run(root, name, seconds=1.0 if "open" in name else 0.3)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def _alter(cfg, corpus):
    def answer(method, rs, served):
        if method == "query_batch":
            out = np.array(served)
            out[:, 0] *= 1.05
            return out
        idx, dist = served
        dist = np.array(dist)
        dist[:, 0] *= 1.05
        return idx, dist
    return answer


def _half_batch(cfg, corpus):
    def answer(method, rs, served):
        h = len(rs) // 2
        if method == "query_batch":
            out = np.array(served)
            out[h:2 * h] = out[:h]
            return out
        idx, dist = (np.array(x) for x in served)
        idx[h:2 * h], dist[h:2 * h] = idx[:h], dist[:h]
        return idx, dist
    return answer


def _drop_neighbour(cfg, corpus):
    n = corpus.cols.shape[0]

    def answer(method, rs, served):
        idx, dist = (np.array(x) for x in served)
        for i in range(idx.shape[0]):
            extra = next(j for j in range(n) if j not in idx[i])
            idx[i] = np.append(idx[i][1:], extra)
            dist[i] = np.append(dist[i][1:], dist[i][-1])
        return idx, dist
    return answer


@pytest.mark.parametrize("name,fault", [
    ("tiny_paper_5k.full_bulk", _alter),
    ("tiny_paper_5k.full_bulk", _half_batch),
    ("tiny_paper_5k.documents_bulk", _alter),
    ("tiny_paper_5k.documents_bulk", _half_batch),
    ("tiny_paper_5k.topk_bulk", _alter),
    ("tiny_paper_5k.topk_bulk", _half_batch),
    ("tiny_paper_5k.topk_bulk", _drop_neighbour),
    ("tiny_paper_5k.topk_open", _drop_neighbour)])
def test_a_broken_timed_path_is_not_correct(root, name, fault):
    line = _run(root, name, seconds=1.0 if "open" in name else 0.3,
                answer=fault)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("name", ["tiny_paper_5k.full_bulk",
                                  "tiny_paper_5k.documents_bulk",
                                  "tiny_paper_5k.topk_bulk"])
def test_the_bfloat16_control_is_not_correct(root, name):
    line = _run(root, name, answer=cell.control_answers)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
