"""CAGRA: build from a config's ``index`` block and serve the graph walk
(no refine: the index holds the f32 rows and every distance is exact)."""

from __future__ import annotations


def build(cfg: dict, base):
    """(MutableIndex, graph shapes) over the device rows ``base``."""
    import jax

    from raft_tpu import kernels, serve
    from raft_tpu.neighbors import cagra

    if not hasattr(kernels, "stamp_search_work"):
        # only to save a build of minutes that cannot pass: the program
        # that brought the walk counter also brought the hop's
        # full-precision row norms, and a program older than both scores
        # them at one bf16 pass on the chip, so `correct` would refuse
        # its run by dist_err after the build (4.4e-4 against the 2e-5
        # limit, PERF.md)
        raise SystemExit("cagra cells need a program that counts its graph "
                         "walk (raft_tpu.kernels.stamp_search_work)")
    ix = cfg["index"]
    if ix.get("refine"):
        raise ValueError("cagra cells take no refine")
    params = cagra.IndexParams(metric=cfg["metric"], **ix["build"])
    index = cagra.build(params, base)
    jax.block_until_ready(index.graph)
    mi = serve.MutableIndex(
        index, search_params=cagra.SearchParams(**ix["search"]))
    shapes = {"n_rows": int(base.shape[0]), "width": int(base.shape[1]),
              "graph_degree": int(index.graph_degree),
              "itopk": int(ix["search"]["itopk_size"]),
              "search_width": int(ix["search"]["search_width"]),
              "entry_points": (0 if index.entry_ids is None
                               else int(index.entry_ids.shape[0])),
              "elem_bytes": int(ix["scan_elem_bytes"])}
    return mi, shapes
