"""comms_t-shaped facade over shard_map collectives.

Verb mapping (ref: core/comms.hpp:125-232 → XLA):

  allreduce      → lax.psum / pmax / pmin           (ICI all-reduce)
  bcast(root)    → select root shard + psum trick   (broadcast)
  reduce(root)   → psum, value meaningful at root   (XLA keeps it replicated)
  allgather      → lax.all_gather                   (ICI all-gather)
  gather(root)   → all_gather (root reads)
  reducescatter  → lax.psum_scatter                 (ICI reduce-scatter)
  device_send/recv → lax.ppermute                   (neighbor exchange)
  sync_stream    → jax.block_until_ready
  comm_split     → mesh sub-axes (a Comms bound to a different axis name)
  barrier        → psum of a scalar + block

Usage: algorithms accept a ``Comms`` giving the mesh axis name(s), and run
inside ``shard_map``; outside shard_map the class still answers rank/size
queries for orchestration code.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P


def make_mesh(
    n_devices: Optional[int] = None,
    axis_names: Tuple[str, ...] = ("data",),
    shape: Optional[Tuple[int, ...]] = None,
) -> Mesh:
    """Build a device mesh over the first n local devices.

    The analog of nccl_clique construction over all visible GPUs
    (ref: comms/nccl_clique.hpp) — in JAX one process natively drives all
    local TPU cores.
    """
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    devs = devs[:n_devices]
    if shape is None:
        shape = (n_devices,) + (1,) * (len(axis_names) - 1)
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, axis_names)


@dataclass
class Comms:
    """Collective verbs bound to a mesh axis (ref: comms_t facade,
    core/comms.hpp:125)."""

    mesh: Mesh
    axis: str = "data"

    # -- topology ----------------------------------------------------------
    def get_size(self) -> int:
        return self.mesh.shape[self.axis]

    def get_rank(self) -> jax.Array:
        """Callable inside shard_map only (trace-time rank index)."""
        return lax.axis_index(self.axis)

    def comm_split(self, axis: str) -> "Comms":
        """Sub-communicator = different mesh axis (ref: comms_t::comm_split,
        stored via core/resource/sub_comms.hpp)."""
        if axis not in self.mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh {self.mesh.axis_names}")
        return Comms(self.mesh, axis)

    # -- collectives (inside shard_map) ------------------------------------
    def allreduce(self, x: jax.Array, op: str = "sum") -> jax.Array:
        if op == "sum":
            return lax.psum(x, self.axis)
        if op == "max":
            return lax.pmax(x, self.axis)
        if op == "min":
            return lax.pmin(x, self.axis)
        if op == "prod":
            # sign-aware: magnitude via log-sum-exp, sign via parity of
            # negative count, zero if any shard contributes a zero
            mag = jnp.exp(lax.psum(jnp.log(jnp.maximum(jnp.abs(x), 1e-300)), self.axis))
            neg_parity = lax.psum((x < 0).astype(jnp.int32), self.axis) % 2
            sign = jnp.where(neg_parity == 1, -1.0, 1.0)
            any_zero = lax.pmax((x == 0).astype(jnp.int32), self.axis)
            return jnp.where(any_zero == 1, jnp.zeros_like(x), sign * mag)
        raise ValueError(f"unsupported reduce op {op!r}")

    def bcast(self, x: jax.Array, root: int = 0) -> jax.Array:
        rank = lax.axis_index(self.axis)
        contrib = jnp.where(rank == root, x, jnp.zeros_like(x))
        return lax.psum(contrib, self.axis)

    def reduce(self, x: jax.Array, root: int = 0, op: str = "sum") -> jax.Array:
        # XLA has no rooted reduce; all-reduce and let non-roots ignore it
        return self.allreduce(x, op)

    def allgather(self, x: jax.Array, *, axis: int = 0, tiled: bool = True) -> jax.Array:
        return lax.all_gather(x, self.axis, axis=axis, tiled=tiled)

    def gather(self, x: jax.Array, root: int = 0, *, axis: int = 0) -> jax.Array:
        return self.allgather(x, axis=axis)

    def allgatherv(self, x_padded: jax.Array, lengths: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Variable-length allgather: shards padded to a common max
        (static shapes); returns (gathered padded [size, max, ...], lengths).
        (ref: comms_t::allgatherv — XLA needs static shapes, so callers keep
        the lengths mask.)"""
        g = lax.all_gather(x_padded, self.axis)
        l = lax.all_gather(lengths, self.axis)
        return g, l

    def reducescatter(self, x: jax.Array, *, tiled: bool = True) -> jax.Array:
        return lax.psum_scatter(x, self.axis, tiled=tiled)

    def device_sendrecv(self, x: jax.Array, dest_offset: int = 1) -> jax.Array:
        """Ring neighbor exchange via ppermute (ref: comms_t::device_sendrecv;
        the building block the reference uses for ring algorithms)."""
        n = self.get_size()
        perm = [(i, (i + dest_offset) % n) for i in range(n)]
        return lax.ppermute(x, self.axis, perm)

    def alltoall(self, x: jax.Array, *, split_axis: int = 0, concat_axis: int = 0) -> jax.Array:
        return lax.all_to_all(
            x, self.axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True
        )

    def barrier_value(self) -> jax.Array:
        """In-graph barrier token (sum of ones)."""
        return lax.psum(jnp.ones(()), self.axis)

    # -- host-side ---------------------------------------------------------
    def sync_stream(self, *arrays) -> None:
        """Blocking sync; a cancellation point like the reference's
        comms-aware interruptible::synchronize."""
        from raft_tpu.core import interruptible as _intr

        _intr.check()
        if arrays:
            jax.block_until_ready(arrays)
        else:
            # real fence: round-trip a tiny transfer so all queued work drains
            jax.block_until_ready(jax.device_put(np.zeros(())))
        _intr.check()


def local_comms(n_devices: Optional[int] = None) -> Comms:
    """One-process multi-device communicator over all local devices —
    the nccl_clique analog (ref: comms/nccl_clique.hpp)."""
    return Comms(make_mesh(n_devices))


# ---- collective self-tests ------------------------------------------------
# The reference exposes runnable collective self-tests to Python for cluster
# validation (ref: comms/comms_test.hpp:33-107, raft_dask comms_utils.pyx:79).
# Same here: each returns True iff the collective produced the expected value
# on every shard.


def _run(comms: Comms, fn, out_specs=P()):
    m = comms.mesh
    f = _shard_map(fn, mesh=m, in_specs=(), out_specs=out_specs, check_vma=False)
    return f()


def _local(x: jax.Array) -> np.ndarray:
    """Concatenate this process's addressable shards.

    In multi-process SPMD the global array spans non-addressable devices;
    each rank validates its own shards (the reference's self-tests likewise
    check per-rank results — comms/detail/test.hpp:41)."""
    shards = sorted(x.addressable_shards, key=lambda s: s.index)
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)


def perform_test_comms_allreduce(comms: Comms) -> bool:
    n = comms.get_size()

    def body():
        v = comms.allreduce(jnp.ones(()))
        return (v == n).astype(jnp.int32)[None]

    return bool(np.all(_local(_run(comms, body, P(comms.axis)))))


def perform_test_comms_bcast(comms: Comms, root: int = 0) -> bool:
    def body():
        rank = comms.get_rank()
        mine = jnp.where(rank == root, 42.0, 0.0)
        got = comms.bcast(mine, root)
        return (got == 42.0).astype(jnp.int32)[None]

    return bool(np.all(_local(_run(comms, body, P(comms.axis)))))


def perform_test_comms_allgather(comms: Comms) -> bool:
    n = comms.get_size()

    def body():
        rank = comms.get_rank()
        g = comms.allgather(rank[None].astype(jnp.float32))
        return jnp.all(g == jnp.arange(n, dtype=jnp.float32)).astype(jnp.int32)[None]

    return bool(np.all(_local(_run(comms, body, P(comms.axis)))))


def perform_test_comms_reduce(comms: Comms, root: int = 0) -> bool:
    n = comms.get_size()

    def body():
        v = comms.reduce(jnp.ones(()), root)
        return (v == n).astype(jnp.int32)[None]

    return bool(np.all(_local(_run(comms, body, P(comms.axis)))))


def perform_test_comms_reducescatter(comms: Comms) -> bool:
    n = comms.get_size()

    def body():
        x = jnp.ones((n,))
        v = comms.reducescatter(x)
        return jnp.all(v == n).astype(jnp.int32)[None]

    return bool(np.all(_local(_run(comms, body, P(comms.axis)))))


def perform_test_comms_send_recv(comms: Comms) -> bool:
    n = comms.get_size()

    def body():
        rank = comms.get_rank()
        got = comms.device_sendrecv(rank.astype(jnp.float32))
        expect = jnp.mod(rank.astype(jnp.float32) - 1, n)
        return (got == expect).astype(jnp.int32)[None]

    return bool(np.all(_local(_run(comms, body, P(comms.axis)))))


def perform_test_comms_allgatherv(comms: Comms, max_len: int = 4) -> bool:
    """Rank r contributes (r+1) valid elements of value r, padded to max_len;
    every rank must reconstruct the full ragged set (ref: comms_t::allgatherv,
    comms/comms_test.hpp test_collective_allgatherv)."""
    n = comms.get_size()

    def body():
        rank = comms.get_rank()
        length = rank + 1
        vals = jnp.where(
            jnp.arange(max_len) < length, rank.astype(jnp.float32), jnp.nan
        )
        g, lens = comms.allgatherv(vals, length[None])
        ok = jnp.ones((), jnp.int32)
        for r in range(n):
            valid = jnp.where(jnp.arange(max_len) < lens[r, 0], g[r], float(r))
            ok = ok & jnp.all(valid == float(r)).astype(jnp.int32)
            ok = ok & (lens[r, 0] == r + 1).astype(jnp.int32)
        return ok[None]

    return bool(np.all(_local(_run(comms, body, P(comms.axis)))))


def perform_test_comm_split(comms: Comms, axis: str) -> bool:
    """Collectives on a split sub-communicator reduce only over that axis
    (ref: comms_t::comm_split + sub_comms resource)."""
    sub = comms.comm_split(axis)
    n_sub = sub.get_size()
    specs = P(*comms.mesh.axis_names)

    def body():
        v = sub.allreduce(jnp.ones(()))
        out = (v == n_sub).astype(jnp.int32)
        for _ in comms.mesh.axis_names:
            out = out[None]
        return out

    return bool(np.all(_local(_run(comms, body, specs))))
