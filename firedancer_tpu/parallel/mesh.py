"""Multi-chip sharding for the verify pipeline (Mesh + shard_map).

The reference scales sigverify by running N independent quic+verify tile
pairs on N cores (config verify_tile_count,
/root/reference/src/app/fdctl/config/default.toml:297-299, and
configure/frank.c:215-224). The TPU-native equivalent: ONE logical verify
stage whose batch axis is sharded data-parallel over the device mesh ('dp'),
with diagnostic counters reduced over ICI via psum — XLA inserts the
collectives; there is no NCCL/MPI analog to port (the reference's tango
rings stay host-side, see firedancer_tpu.tango).

Multi-host extension: the same Mesh spans hosts via jax.distributed; 'dp'
collectives then ride ICI within a slice and DCN across slices, preserving
tango's philosophy (lossy broadcast stays host-local; only counter
reduction crosses the wire).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.verify import verify_batch


def shard_map_nocheck(f, mesh, in_specs, out_specs):
    """shard_map with replication checking off: our steps combine
    per-shard point partials with explicit collectives and declare the
    results replicated, which the static inference cannot verify."""
    return shard_map(f, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def make_mesh(n_devices: int | None = None, axis: str = "dp") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"make_mesh: requested {n_devices} devices but only "
                f"{len(devs)} available ({devs[0].platform}); refusing to "
                "silently shrink the mesh — set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N for a "
                "virtual CPU mesh"
            )
        devs = devs[:n_devices]
    import numpy as np

    return Mesh(np.asarray(devs), (axis,))


def _mesh_axis(mesh: Mesh):
    """The collective axis for a verify mesh: the bare axis NAME on the
    classic single-axis 'dp' mesh (so every audited single-axis graph —
    and its lint_graph_cert.json certificate — is bit-identical to
    before), the axis-name TUPLE on a multi-axis fd_fabric mesh
    (('host', 'dp')): jax.lax collectives and PartitionSpecs both accept
    the tuple, sharding/reducing over host x dp jointly."""
    names = mesh.axis_names
    return names[0] if len(names) == 1 else tuple(names)


def verify_step_sharded(mesh: Mesh):
    """Build the jitted, mesh-sharded verify step.

    Returns fn(msgs, lens, sigs, pubs) -> (statuses, diag) where diag is a
    dict of globally-psum'd counters mirroring the reference's fseq diag ABI
    (PUB_CNT / FILT_CNT, fd_fseq.h:57-63).
    """
    axis = mesh.axis_names[0]

    def step(msgs, lens, sigs, pubs):
        statuses = verify_batch(msgs, lens, sigs, pubs)
        ok = (statuses == 0).astype(jnp.int32)
        diag = {
            "pub_cnt": jax.lax.psum(jnp.sum(ok), axis),
            "filt_cnt": jax.lax.psum(jnp.sum(1 - ok), axis),
            "pub_sz": jax.lax.psum(jnp.sum(ok * lens), axis),
        }
        return statuses, diag

    spec = P(axis)
    sharded = shard_map_nocheck(
        step,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, P()),
    )
    return jax.jit(sharded)


def verify_rlc_step_sharded(mesh: Mesh, plan=None):
    """Build the jitted, mesh-sharded RLC batch-verify pass (round-10:
    the primary verify mode finally composes with multi-chip).

    Per-lane stages (s-range, decompress, SHA/sc front-end, status
    ladder) shard trivially over 'dp'; the Pippenger MSMs and the
    torsion certification fill buckets LOCALLY per device and combine
    per-window/per-trial point partials across the mesh with one
    all_gather + unified adds before the doubling-chain tails
    (ops/msm.py axis_name plumbing). The u*B term folds per shard —
    sum_d u_d*B == (sum_d u_d)*B in the group — so no scalar collective
    is needed.

    Returns fn(msgs, lens, sigs, pubs, z, u) -> (status, definite,
    batch_ok) with the exact verify_batch_rlc contract: status/definite
    per-lane (global batch order), batch_ok the replicated global
    verdict. z is (B, 32) per-lane weights; u is (K, 2B) with columns
    0..B-1 weighting the pubkey points and B..2B-1 the R points —
    i.e. a drop-in rlc_fn for verify_rlc.make_async_verifier.

    plan (None = msm.active_plan()): the fd_msm2 MSM schedule, resolved
    ONCE at build time so every shard traces the identical window
    grid — the per-window partials the mesh gathers must agree in
    shape across devices by construction.
    """
    from ..ops.msm import active_plan
    from ..ops.verify_rlc import verify_batch_rlc

    axis = _mesh_axis(mesh)
    if plan is None:
        plan = active_plan()

    def step(msgs, lens, sigs, pubs, z, u3):
        # u3: (K, 2, B_local) — axis 1 separates A-weights from
        # R-weights so the lane shard of each half lands on the right
        # device; restack to the local (K, 2*B_local) column order
        # verify_batch_rlc's stacked [A-lanes, R-lanes] decompression
        # expects.
        u = u3.reshape(u3.shape[0], -1)
        return verify_batch_rlc(msgs, lens, sigs, pubs, z, u,
                                axis_name=axis, plan=plan)

    spec = P(axis)
    sharded = shard_map_nocheck(
        step,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, P(None, None, axis)),
        out_specs=(spec, spec, P()),
    )
    jitted = jax.jit(sharded)

    def fn(msgs, lens, sigs, pubs, z, u):
        return jitted(msgs, lens, sigs, pubs, z, _u_blocks(u, msgs.shape[0]))

    return fn


def _u_blocks(u, bsz: int):
    """The torsion weights in the (K, 2, B) block layout the sharded
    graphs take: a (K, 2B) u (columns 0..B-1 the A weights, B..2B-1 the
    R weights, verify_rlc.fresh_u's convention) is reshaped; a u a mesh
    engine already placed as (K, 2, B), sharded by lane, passes as it
    is, so no chip receives another's columns."""
    if u.ndim == 3:
        return u
    return u.reshape(u.shape[0], 2, bsz)


def verify_rlc_split_sharded(mesh: Mesh, plan=None):
    """The fd_pod double-buffer pair: the mesh-sharded RLC pass as TWO
    separately-jitted graphs (round-18, ROADMAP direction 1) —

      local_fill(msgs, lens, sigs, pubs, z, u)
          -> (status, definite, parts)
          per-shard SHA/decompress/status ladder + the three Pippenger
          bucket fills, NO collectives (ops/verify_rlc.verify_rlc_local
          under shard_map). status/definite are the global per-lane
          arrays; parts is the pytree of per-shard window/trial
          partials, stacked on a leading mesh axis ((N, 32, nw)-limb
          coords, (N,) fill flags).

      combine_tail(parts) -> batch_ok
          ONE all_gather of the tiny partials + unified adds + the
          doubling-chain tails (verify_rlc_combine under shard_map,
          axis_name threaded) — the replicated global verdict.

    Why two graphs: the collectives (and the serial doubling chains
    they feed) live entirely in combine_tail, so a dispatcher can have
    batch k's combine_tail executing while batch k+1's local_fill is
    already dispatched — wiredancer's DMA-slot double-buffering, stolen
    for the mesh (SZKP/ZK-Flex schedule many bucket-fill units against
    one work stream the same way). Composition is bit-exact with
    verify_rlc_step_sharded: local/combine are the monolithic step's
    own body factored at the collective boundary, and the cross-shard
    fold goes through the one msm.combine_stacked rule either way.

    Both callables take/produce global arrays with the exact
    verify_batch_rlc argument convention (u is (K, 2B); the A/R-half
    resharding happens inside, as in the monolithic builder). plan is
    resolved once at build time, like verify_rlc_step_sharded — both
    jitted halves bake the same window grid.
    """
    local_jit, combine_jit = _rlc_split_jits(mesh, plan)

    def local_fill(msgs, lens, sigs, pubs, z, u):
        return local_jit(msgs, lens, sigs, pubs, z,
                         _u_blocks(u, msgs.shape[0]))

    return local_fill, combine_jit


def _rlc_split_jits(mesh: Mesh, plan=None):
    """The shared split-pair builder: (local_jit, combine_jit) taking
    the native (K, 2, B) u3 layout. verify_rlc_split_sharded wraps
    local_jit with the host-side (K, 2B) reshape; verify_rlc_split_global
    hands the raw pair to the fabric."""
    from ..ops.msm import active_plan
    from ..ops.verify_rlc import verify_rlc_combine, verify_rlc_local

    axis = _mesh_axis(mesh)
    if plan is None:
        plan = active_plan()

    # The two programs are named for the chip's trace: its module line
    # shows them as jit_pod_local and jit_pod_tail.
    def pod_local(msgs, lens, sigs, pubs, z, u3):
        u = u3.reshape(u3.shape[0], -1)
        status, definite, parts = verify_rlc_local(
            msgs, lens, sigs, pubs, z, u, plan=plan)
        # Stack each partial on a fresh leading mesh axis so the
        # out_spec can concatenate shards: global shape (N, ...).
        stacked = jax.tree_util.tree_map(lambda c: c[None], parts)
        return status, definite, stacked

    def pod_tail(parts):
        # Each shard holds its own (1, ...) slice; drop the carrier
        # axis and let the combine's all_gather rebuild the (N, ...)
        # stack in mesh order — the collective lives HERE, not in
        # local_fill.
        own = jax.tree_util.tree_map(lambda c: c[0], parts)
        return verify_rlc_combine(own, axis_name=axis, plan=plan)

    spec = P(axis)
    parts_spec = _rlc_parts_spec(axis)
    local_sharded = shard_map_nocheck(
        pod_local,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, P(None, None, axis)),
        out_specs=(spec, spec, parts_spec),
    )
    combine_sharded = shard_map_nocheck(
        pod_tail,
        mesh=mesh,
        in_specs=(parts_spec,),
        out_specs=P(),
    )
    local_jit = jax.jit(local_sharded)
    combine_jit = jax.jit(combine_sharded)
    return local_jit, combine_jit


def verify_rlc_split_global(mesh: Mesh, plan=None):
    """The split pair with the NATIVE (K, 2, B) u layout — the
    fd_fabric entry point.

    verify_rlc_split_sharded's convenience wrapper reshapes a host
    (K, 2B) u into the (K, 2, B) block layout before handing it to the
    jitted graph. A multi-process fabric cannot do that: every batch
    input is a global jax.Array assembled with
    jax.make_array_from_process_local_data (each host contributes only
    its own lane block), and reshaping a (K, 2B) global array across
    processes is a cross-host relayout, not a view. So the fabric
    builds each host's (K, 2, B_local) block directly and calls the
    raw jitted pair returned here:

      local_jit(msgs, lens, sigs, pubs, z, u3) -> (status, definite,
          parts)        u3 global (K, 2, B), sharded P(None, None, axes)
      combine_jit(parts) -> batch_ok

    Trace-identical to verify_rlc_split_sharded's graphs (same
    pod_local/pod_tail bodies, same specs); only the host-side
    reshape convenience is dropped.
    """
    local_jit, combine_jit = _rlc_split_jits(mesh, plan)
    return local_jit, combine_jit


def _rlc_parts_spec(axis):
    """The shard_map spec pytree for verify_rlc_local's partials: every
    leaf (point-coord stacks and fill flags alike) shards its leading
    mesh axis. `axis` is a name or a name-tuple (_mesh_axis)."""
    coord = P(axis)
    return {
        "w_r": (coord, coord, coord, coord), "ok_r": P(axis),
        "w_m": (coord, coord, coord, coord), "ok_m": P(axis),
        "sub": (coord, coord, coord, coord), "sub_ok": P(axis),
    }
