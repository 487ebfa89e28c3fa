"""IVF-Flat: build from a config's ``index`` block (no refine: the lists
hold the f32 rows themselves)."""

from __future__ import annotations


def build(cfg: dict, base):
    """(MutableIndex, scan shapes) over the device rows ``base``."""
    import jax

    from raft_tpu import serve
    from raft_tpu.neighbors import ivf_flat

    ix = cfg["index"]
    if ix.get("refine"):
        raise ValueError("ivf_flat cells take no refine")
    params = ivf_flat.IndexParams(metric=cfg["metric"], **ix["build"])
    index = ivf_flat.build(params, base)
    jax.block_until_ready(index.list_data)
    mi = serve.MutableIndex(
        index, search_params=ivf_flat.SearchParams(**ix["search"]))
    shapes = {"n_rows": int(base.shape[0]), "n_lists": int(index.n_lists),
              "n_probes": int(ix["search"]["n_probes"]),
              "width": int(base.shape[1]),
              "elem_bytes": int(ix["scan_elem_bytes"])}
    return mi, shapes
