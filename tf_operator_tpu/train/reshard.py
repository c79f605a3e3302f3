"""Device-state re-shard for elastic resizes (r19).

When a resize directive lands, every surviving member must rebuild its
device-resident params + optimizer state for the NEW world. Two sources
feed the rebuild, and the distinction is the whole design:

- **Re-laid-out** rows: state this member's device copy is already
  authoritative for (rows it consumed itself, or refreshed at the last
  barrier). These move device-to-device through a pjit re-layout — no
  host round-trip, no disk.
- **Re-fetched** rows: state some OTHER member advanced since our last
  refresh. The authoritative copy lives in the shared row store (one
  atomically-written ``.npy`` per row); a re-grown member with no device
  state at all first restores the chief's last committed checkpoint
  through the world-size-tagged shard depot (peer depot -> local disk,
  ``rendezvous.statechannel.choose_restore_source`` order) and then
  overlays the row store on top.

The soak's model is deliberately tiny — params are a ``(total, D)``
float32 matrix and the optimizer state one momentum scalar per row, each
row touched by exactly one consume — so "bit-identical final params vs
the uninterrupted run" is a meaningful hard gate across any composition
of shrinks, re-grows, preemptions, and grow-beyond-spec epochs: every
update is row-local and deterministic, so any lost, duplicated, or
mis-sourced row changes the digest.

``jax`` arrays are built with ``jax.make_array_from_callback`` against a
local 1-device ``dp`` mesh (the CI data plane — one process, one CPU
device), and the re-layout goes through ``jax.jit`` with
``out_shardings``; the row-update body runs under
``parallel/collectives.shard_map`` so the same code shape lifts to a
real multi-device mesh.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

# Default row width of the soak model's params matrix.
PARAM_DIM = 8
# The row update: row' = decay * row + lr * w, momentum' = lr * w. Chosen
# so the final value depends on the init row AND the consumed window —
# a row sourced from the wrong place cannot collide with the right one.
ROW_DECAY = 0.5
ROW_LR = 1e-3


# ---- local device mesh -------------------------------------------------


def local_mesh():
    """1-device ``dp`` mesh over the first local device. The soak's data
    plane is one process per member (CI cannot run multi-process SPMD),
    so each member's "shard" is a full replica on its own device; the
    sharding machinery below is exactly what a >1-device member would
    run with a non-trivial PartitionSpec."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]), ("dp",))


def replicated_sharding(mesh):
    import jax

    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def rows_to_device(host: np.ndarray, sharding):
    """Host rows -> device array via ``jax.make_array_from_callback`` —
    each addressable device pulls exactly its index slice, which is what
    keeps this path host-memory-flat on a real sharded mesh."""
    import jax

    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx]
    )


def relayout(arr, sharding):
    """pjit re-layout onto ``sharding`` (device-to-device when possible):
    the "re-laid-out" half of a re-shard."""
    import jax

    return jax.jit(lambda x: x, out_shardings=sharding)(arr)


def device_to_host(arr) -> np.ndarray:
    return np.asarray(arr)


# ---- deterministic row model -------------------------------------------


def init_row(seed: int, p: int, dim: int = PARAM_DIM) -> np.ndarray:
    """Deterministic init for row ``p``: every member of every
    incarnation derives the identical value (SeedSequence over the
    (seed, position) pair)."""
    rng = np.random.default_rng([int(seed), int(p)])
    return rng.standard_normal(dim).astype(np.float32)


def make_row_update() -> Callable:
    """The jitted one-touch row update. Runs the body through
    shard_map over the local mesh so the identical code shape lifts to
    a real dp mesh; on the 1-device mesh the spec is fully replicated
    and the wrapper is an identity layout."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tf_operator_tpu.parallel.collectives import shard_map

    mesh = local_mesh()

    def body(row, mom, w):
        new_row = ROW_DECAY * row + ROW_LR * w
        new_mom = ROW_LR * w * jnp.ones_like(mom)
        return new_row, new_mom

    shard = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=(P(), P()),
    )
    return jax.jit(shard)


# ---- shared row store --------------------------------------------------


def state_dir(workdir: str) -> str:
    return os.path.join(workdir, "state")


def row_path(sdir: str, p: int) -> str:
    return os.path.join(sdir, f"row-{int(p):06d}.npy")


def write_row(sdir: str, p: int, row: np.ndarray, mom: float) -> None:
    """Durably publish row ``p``: momentum scalar appended to the row,
    written tmp-then-rename so a member killed mid-write leaves either
    the old row or nothing — never a torn one. Written BEFORE the
    consumption record, so a durable record implies a durable row."""
    os.makedirs(sdir, exist_ok=True)
    buf = np.concatenate(
        [np.asarray(row, dtype=np.float32).ravel(),
         np.asarray([mom], dtype=np.float32)]
    )
    tmp = row_path(sdir, p) + f".tmp-{os.getpid()}"
    with open(tmp, "wb") as f:
        np.save(f, buf)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, row_path(sdir, p))


def read_row(
    sdir: str, p: int, dim: int = PARAM_DIM
) -> Optional[Tuple[np.ndarray, float]]:
    try:
        buf = np.load(row_path(sdir, p))
    except (OSError, ValueError):
        return None
    if buf.shape != (dim + 1,):
        return None
    return buf[:dim].astype(np.float32), float(buf[dim])


# ---- the re-shard itself -----------------------------------------------


@dataclass
class ReshardPlan:
    """What a rebuild did, row by row — the soak's receipt that the
    re-shard actually re-laid-out device state rather than round-tripping
    everything through the filesystem."""
    relaid: int = 0      # rows taken from this member's own device copy
    refetched: int = 0   # rows read back from the shared row store
    inited: int = 0      # rows nobody has consumed yet (deterministic init)
    epochs: List[int] = field(default_factory=list)
    # Rows whose rebuilt device value is FINAL (relaid or refetched): the
    # one-touch update means a consumed row never changes again, so these
    # stay authoritative across every later rebuild. Init rows are NOT
    # authoritative — another member may consume them after this barrier.
    authoritative: Set[int] = field(default_factory=set)

    def merge(self, other: "ReshardPlan") -> None:
        self.relaid += other.relaid
        self.refetched += other.refetched
        self.inited += other.inited
        self.epochs.extend(other.epochs)


def plan_rows(
    total: int, fresh: Set[int]
) -> Tuple[List[int], List[int]]:
    """Split [0, total) into (kept, stale): kept rows re-layout from the
    member's device copy, stale rows re-fetch from the row store."""
    kept = [p for p in range(total) if p in fresh]
    stale = [p for p in range(total) if p not in fresh]
    return kept, stale


def rebuild_state(
    total: int,
    dim: int,
    seed: int,
    sdir: str,
    device_params,
    device_mom,
    fresh: Set[int],
    sharding,
    epoch: int = 0,
) -> Tuple[object, object, ReshardPlan]:
    """Rebuild the full (total, dim) params + (total,) momentum device
    arrays for a new epoch.

    Source order per row: this member's own device copy when the row is
    still fresh (re-laid-out), else the shared row store (re-fetched),
    else the deterministic init (never consumed). Returns the new device
    arrays and the plan receipt."""
    plan = ReshardPlan(epochs=[epoch])
    kept, stale = plan_rows(total, fresh)
    host_params = np.empty((total, dim), dtype=np.float32)
    host_mom = np.zeros((total,), dtype=np.float32)
    if kept:
        # One device->host pull for every kept row, then the re-layout
        # below pushes the assembled matrix back through pjit — on a
        # >1-device mesh the callback form keeps this per-shard.
        cur_p = device_to_host(device_params) if device_params is not None else None
        cur_m = device_to_host(device_mom) if device_mom is not None else None
        for p in kept:
            host_params[p] = cur_p[p]
            host_mom[p] = cur_m[p]
            plan.relaid += 1
            plan.authoritative.add(p)
    for p in stale:
        got = read_row(sdir, p, dim)
        if got is not None:
            host_params[p], host_mom[p] = got
            plan.refetched += 1
            plan.authoritative.add(p)
        else:
            host_params[p] = init_row(seed, p, dim)
            plan.inited += 1
    new_params = relayout(rows_to_device(host_params, sharding), sharding)
    new_mom = relayout(rows_to_device(host_mom, sharding), sharding)
    return new_params, new_mom, plan


def assemble_final(
    total: int, dim: int, seed: int, sdir: str
) -> np.ndarray:
    """The chief's final assembly: every row from the row store (all
    consumed by the time the coverage gate passes), init where a row is
    genuinely absent. Pure host-side — the digest input."""
    out = np.empty((total, dim), dtype=np.float32)
    for p in range(total):
        got = read_row(sdir, p, dim)
        out[p] = got[0] if got is not None else init_row(seed, p, dim)
    return out


def expected_params(
    total: int, dim: int, seed: int, order: Sequence[int]
) -> np.ndarray:
    """The uninterrupted run's final params: the SAME jitted row update
    applied once per position (each row is touched exactly once and the
    update is row-local, so consumption order cannot matter). Routed
    through the identical compiled function as the live members — a
    host-side re-derivation could differ in the last bit if XLA fuses
    the multiply-add, and "bit-identical" means bit-identical."""
    import jax.numpy as jnp

    update = make_row_update()
    out = np.empty((total, dim), dtype=np.float32)
    zero_mom = jnp.zeros((), jnp.float32)
    for p in range(total):
        row, _ = update(
            jnp.asarray(init_row(seed, p, dim)),
            zero_mom,
            jnp.asarray(float(int(order[p])), jnp.float32),
        )
        out[p] = np.asarray(row)
    return out


def params_digest(params: np.ndarray) -> str:
    """Sha256 over the row-major float32 bytes — bit-identical or bust."""
    import hashlib

    return hashlib.sha256(
        np.ascontiguousarray(params, dtype=np.float32).tobytes()
    ).hexdigest()
