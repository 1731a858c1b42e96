"""The necessary-bytes function against the program's work model: its
two terms are the streamed-row terms of ``workmodel``'s fuse-level-2
router and scorer bytes, on the reduced configuration."""
import numpy as np
import pytest

from benchtest_util import spec  # noqa: F401  (puts bench/ on sys.path)
from lib import workbytes
from repro.configs.seismic_msmarco import REDUCED
from repro.core.build import index_shape
from repro.retrieval import workmodel


@pytest.fixture(scope="module")
def shape():
    return index_shape(REDUCED.n_docs, REDUCED.dim, REDUCED.doc_nnz,
                       REDUCED.index)


@pytest.mark.parametrize("cut", [1, 8])
def test_summary_term_is_the_router_stream(shape, cut):
    cfg = REDUCED.index
    rows = cut * cfg.n_blocks
    fused = workmodel.router_bytes(cut=cut, n_blocks=cfg.n_blocks,
                                   summary_nnz=cfg.summary_nnz,
                                   dim=REDUCED.dim, fuse_level=2)
    streamed = fused - 4 * REDUCED.dim - 4 * rows
    got = workbytes.necessary_bytes([rows], [0],
                                    workbytes.summary_row_bytes(shape),
                                    workbytes.forward_row_bytes(shape))
    assert got[0] == streamed


@pytest.mark.parametrize("scored", [0, 37, 512])
def test_forward_term_is_the_scorer_stream(shape, scored):
    n_slots = 16 * REDUCED.index.block_cap
    fused = workmodel.scorer_bytes(n_slots=n_slots, scored_slots=scored,
                                   nnz=REDUCED.doc_nnz, quant=False,
                                   dim=REDUCED.dim, fuse_level=2)
    streamed = fused - 4 * REDUCED.dim - 8 * n_slots
    got = workbytes.necessary_bytes([0], [scored],
                                    workbytes.summary_row_bytes(shape),
                                    workbytes.forward_row_bytes(shape))
    assert got[0] == streamed


def test_live_blocks_count_each_probed_list_once():
    live = np.array([3, 0, 5, 7])
    probed = np.array([[0, 2, 2], [1, 3, 0]])
    assert workbytes.live_blocks_probed(live, probed).tolist() == [8, 10]
