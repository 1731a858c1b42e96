"""The benchmark's data generator draws the same collections as it did
when the cells' bounds were set: sha256 digests of ``make_collection``'s
four arrays, written down from the whole-chunk generator that drew every
earlier run's data, at MS MARCO widths over three chunks (the last one
ragged) and at a CPU-test size with one and two topics a document."""
import hashlib

import pytest

from benchtest_util import BENCH  # noqa: F401  (puts lib on sys.path)
from lib import gen

CASES = {
    # the r90 configuration's corpus widths: 8192 + 8192 + 3616 rows
    "r90-widths": (
        dict(dim=30522, n_docs=20000, n_queries=1024, doc_nnz=128,
             query_nnz=48, n_topics=64, topic_coords=384, zipf_a=1.05,
             value_sigma=1.0, doc_topic_mix=2, seed=2 ** 33 + 7),
        ["0e4497a19e10e7934d57cd9a713b746a1cdf0048a8079d4d0cca0b7b3bd35b51",
         "848294b2773ac5aa5d851f6d69a9ba1e8dcc508b7d6cc5d3d6005802b1d1b5ed",
         "87af7931629dbe2b011422209b6a144c38869552d5e7ae8f099c7ba2746b3bc8",
         "116ec98c6d13bba956d9dcbe6c56f23fca10ec34ef7a7ddc1868daf058b8fe20"]),
    "tiny-mix1": (
        dict(dim=1024, n_docs=9000, n_queries=64, doc_nnz=48, query_nnz=16,
             n_topics=32, topic_coords=128, zipf_a=1.05, value_sigma=1.0,
             doc_topic_mix=1, seed=5),
        ["7741051ffd39bd6a8df570782e2c96e22aca57875fc674072cde9238eef15b31",
         "df1afef3b6d1e77b7154ab8cb840a761f7855cdc9e57717e0f76b8cd0f11144d",
         "b0da2278d51e84d420e6faf650cd164fa214a035b161ef58988b1c64877f2e1e",
         "c005f56b5c46df9194fe1953388dc34437b6744e0ca4db06a84f7fad0a857345"]),
    "tiny-mix2": (
        dict(dim=1024, n_docs=9000, n_queries=64, doc_nnz=48, query_nnz=16,
             n_topics=32, topic_coords=128, zipf_a=1.05, value_sigma=1.0,
             doc_topic_mix=2, seed=5),
        ["7d3c4732409c1342832b9e793e7a3b460d66dda955aa9ae1c50fbe03a4f77202",
         "07bbf888d5f5a6995b91bd803c9eccd6f7c589492c6debb484145ee909c4b237",
         "1eba88c2202708e71b30b591545ff584194952e7d5fa0d0e5a718680f5075d38",
         "3e851930d31ea7bbb6f3ea8c4bc47aa9d5ec2d9d047fbed4b54b8d25245bd5b5"]),
}


def _digests(kw):
    arrays = gen.make_collection(gen.SyntheticSparseConfig(**kw))
    n, nq = kw["n_docs"], kw["n_queries"]
    assert [(a.dtype.str, a.shape) for a in arrays] == [
        ("<i4", (n, kw["doc_nnz"])), ("<f4", (n, kw["doc_nnz"])),
        ("<i4", (nq, kw["query_nnz"])), ("<f4", (nq, kw["query_nnz"]))]
    return [hashlib.sha256(a.tobytes()).hexdigest() for a in arrays]


@pytest.mark.parametrize("case", sorted(CASES))
def test_collection_is_the_one_earlier_runs_drew(case):
    kw, want = CASES[case]
    assert _digests(kw) == want


@pytest.mark.parametrize("row_block,threads", [(1000, 1), (8192, 3),
                                               (37, 16)])
def test_rows_do_not_depend_on_the_block_or_the_threads(monkeypatch,
                                                        row_block, threads):
    """Blocks of other sizes (one the whole chunk, one that leaves a
    ragged block in every chunk) and other thread counts draw the same
    rows."""
    monkeypatch.setattr(gen, "ROW_BLOCK", row_block)
    monkeypatch.setattr(gen, "MAX_THREADS", threads)
    kw, want = CASES["tiny-mix2"]
    assert _digests(kw) == want
