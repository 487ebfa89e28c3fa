"""IVF-PQ: build from a config's ``index`` block, served with exact refine
when the config asks for it."""

from __future__ import annotations


def build(cfg: dict, base):
    """(MutableIndex, scan shapes) over the device rows ``base``."""
    import jax

    from raft_tpu import serve
    from raft_tpu.neighbors import ivf_pq

    ix = cfg["index"]
    params = ivf_pq.IndexParams(metric=cfg["metric"], **ix["build"])
    index = ivf_pq.build(params, base)
    jax.block_until_ready(index.list_data)
    mi = serve.MutableIndex(
        index, search_params=ivf_pq.SearchParams(**ix["search"]),
        refine_dataset=base if ix["refine"] else None)
    shapes = {"n_rows": int(base.shape[0]), "n_lists": int(index.n_lists),
              "n_probes": int(ix["search"]["n_probes"]),
              "width": int(index.rot_dim),
              "elem_bytes": int(ix["scan_elem_bytes"])}
    return mi, shapes
