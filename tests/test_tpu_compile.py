"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode proves what a kernel computes; only Mosaic says whether it
compiles for the chip (tiling, VMEM, loop-carry types).  The TPU compiler
is installed here and compiles for a chip that is described, not attached,
so these run under the CPU test driver at the deployment widths the serving
path uses (DEEP 96-d IVF-PQ, SIFT-scale brute force, CAGRA deg 32).

The topology is described inside a module fixture: only one process may
load the TPU library, and a test worker that loads it at import would make
the others collect different tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from raft_tpu import kernels
from raft_tpu.kernels.cagra_traverse import cagra_fused_hop
from raft_tpu.kernels.fused_argmin import fused_l2_argmin
from raft_tpu.kernels.fused_knn import fused_l2_topk
from raft_tpu.kernels.ivf_scan import ivf_scan_probe_major, ivf_scan_query_major
from raft_tpu.kernels.select_k import select_k_pallas

# IVF list geometry at the one-chip DEEP deployment (n_lists ~ n/500,
# lists padded to a lane-friendly capacity, rot_dim = d = 96)
L, CAP, ROT = 1024, 1024, 96


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache (no device to deserialize for): keep it off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [1024, 8192])
@pytest.mark.parametrize("k", [10, 32])
def test_select_k_compiles(one_chip, n, k):
    _compile(
        lambda s: select_k_pallas(s, k), one_chip, ((1024, n), jnp.float32)
    )


@pytest.mark.parametrize("mode", ["l2", "ip"])
def test_fused_l2_topk_compiles(one_chip, mode):
    _compile(
        lambda q, x, xx: fused_l2_topk(q, x, xx, 10, mode=mode),
        one_chip,
        ((1024, 96), jnp.float32),
        ((1_000_000, 96), jnp.float32),
        ((1_000_000,), jnp.float32),
    )


def test_fused_l2_argmin_compiles(one_chip):
    _compile(
        fused_l2_argmin, one_chip,
        ((1_000_000, 96), jnp.float32),
        ((1024, 96), jnp.float32),
        ((1024,), jnp.float32),
    )


def test_ivf_scan_probe_major_compiles(one_chip):
    B, G = 256, 64
    _compile(
        lambda bl, qg, q2, data, y2, idx: ivf_scan_probe_major(
            bl, qg, q2, data, y2, idx, 40, metric="inner_product",
            scan_dtype="float32",
        ),
        one_chip,
        ((B,), jnp.int32),
        ((B, G, ROT), jnp.float32),
        ((B, G), jnp.float32),
        ((L, CAP, ROT), jnp.bfloat16),
        ((L, CAP), jnp.float32),
        ((L, CAP), jnp.int32),
    )


def test_ivf_scan_query_major_compiles(one_chip):
    Q, P = 1024, 32
    _compile(
        lambda pr, qr, q2, data, y2, idx: ivf_scan_query_major(
            pr, qr, q2, data, y2, idx, 40, metric="inner_product",
            scan_dtype="float32",
        ),
        one_chip,
        ((Q, P), jnp.int32),
        ((Q, ROT), jnp.float32),
        ((Q,), jnp.float32),
        ((L, CAP, ROT), jnp.bfloat16),
        ((L, CAP), jnp.float32),
        ((L, CAP), jnp.int32),
    )


def test_cagra_fused_hop_compiles(one_chip):
    tile, width, itopk = 256, 1, 64
    _compile(
        lambda x, g, q, par, bd, bi, ex: cagra_fused_hop(
            x, g, q, par, bd, bi, ex, metric="sqeuclidean"
        ),
        one_chip,
        ((1_000_000, 128), jnp.float32),
        ((1_000_000, 32), jnp.int32),
        ((tile, 128), jnp.float32),
        ((tile, width), jnp.int32),
        ((tile, itopk), jnp.float32),
        ((tile, itopk), jnp.int32),
        ((tile, itopk), jnp.bool_),
    )


def test_ivf_pq_search_program_compiles(one_chip, monkeypatch):
    """The whole jitted IVF-PQ query-major search the server dispatches
    (coarse GEMM + Pallas select_k + fused scan + postprocess) at the DEEP
    one-chip widths.  The dispatch asks ``jax.devices()`` for its platform,
    which here is the CPU: the test steers it to the chip's branch."""
    from raft_tpu.neighbors import ivf_pq

    monkeypatch.setattr(kernels, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAFT_TPU_PALLAS", raising=False)
    program = jax.jit(
        ivf_pq._search_query_major_pallas.__wrapped__,
        static_argnames=("n_probes", "k", "metric", "scan_dtype", "interpret"),
    )
    _compile(
        lambda q, c, rot, data, y2, idx: program(
            q, c, rot, data, y2, idx, None, 1.0, n_probes=32, k=40,
            metric="inner_product", scan_dtype="float32", interpret=False,
        ),
        one_chip,
        ((1024, 96), jnp.float32),
        ((1998, 96), jnp.float32),
        ((ROT, 96), jnp.float32),
        ((1998, CAP, ROT), jnp.bfloat16),
        ((1998, CAP), jnp.float32),
        ((1998, CAP), jnp.int32),
    )


def _param_copies(text: str, names) -> list:
    """``copy`` instructions that XLA put in for an entry parameter named in
    ``names``: such a copy carries the parameter's own name as its
    ``op_name`` (whether it reads the parameter or an alias of it)."""
    return [
        line.strip()[:160] for line in text.splitlines()
        if " copy(" in line
        and any(f'op_name="{name}"' in line for name in names)
    ]


def _deep_programs(width: int, q: int):
    """(fn, arg specs, resident parameter names) of the three programs a
    DEEP search dispatch runs, over a scan cache and refine rows ``width``
    lanes wide, at a small list count."""
    import importlib

    from raft_tpu.neighbors import ivf_pq

    refine_mod = importlib.import_module("raft_tpu.neighbors.refine")
    n_lists, n_probes = 64, 32
    qm = jax.jit(
        ivf_pq._search_query_major_pallas.__wrapped__,
        static_argnames=("n_probes", "k", "metric", "scan_dtype", "interpret"),
    )
    pm = jax.jit(
        ivf_pq._search_probe_major_pallas.__wrapped__,
        static_argnames=(
            "n_probes", "k", "metric", "bucket", "scan_dtype", "interpret"
        ),
    )
    lists = [
        ((q, 96), jnp.float32),
        ((n_lists, 96), jnp.float32),
        ((ROT, 96), jnp.float32),
        ((n_lists, 1000, width), jnp.bfloat16),
        ((n_lists, 1000), jnp.float32),
        ((n_lists, 1000), jnp.int32),
    ]
    return {
        "query_major": (
            lambda qq, c, rot, list_data, y2, idx: qm(
                qq, c, rot, list_data, y2, idx, None, 1.0, n_probes=n_probes,
                k=40, metric="inner_product", scan_dtype="float32",
                interpret=False,
            ),
            lists, ("list_data",),
        ),
        "probe_major": (
            lambda qq, c, rot, list_data, y2, idx: pm(
                qq, c, rot, list_data, y2, idx, None, 1.0, n_probes=n_probes,
                k=40, metric="inner_product", bucket=16, scan_dtype="float32",
                interpret=False,
            ),
            lists, ("list_data",),
        ),
        "refine": (
            lambda dataset, qq, cand: refine_mod._refine_jit(
                dataset, qq, cand, 10, "inner_product", tile=None
            ),
            [
                ((999_000, width), jnp.float32),
                ((q, 96), jnp.float32),
                ((q, 40), jnp.int32),
            ],
            ("dataset",),
        ),
    }


@pytest.mark.parametrize("program,q", [
    ("query_major", 1), ("query_major", 1024), ("probe_major", 256),
    ("refine", 1), ("refine", 1024),
])
def test_deep_search_programs_read_lane_padded_rows_in_place(
    one_chip, monkeypatch, program, q
):
    """The scan cache and the refine rows are stored lane-padded
    (``_common.lane_pad``): the compiled search and refine programs then
    read them in their default layout, with no whole-array ``copy`` of
    the resident parameter at entry.  At the unpadded DEEP width 96 the
    same programs do relayout it: the check sees the copy it guards."""
    from raft_tpu.neighbors._common import padded_width

    monkeypatch.setattr(kernels, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAFT_TPU_PALLAS", raising=False)
    for width, copied in ((padded_width(ROT), False), (ROT, True)):
        fn, shapes, names = _deep_programs(width, q)[program]
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        copies = _param_copies(text, names)
        assert bool(copies) == copied, (width, copies)
