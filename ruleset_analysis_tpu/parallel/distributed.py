"""Multi-host scale-out: jax.distributed + a (dcn, data) mesh.

The reference scales across machines by letting YARN fork mapper processes
on every node and shuffling over TCP (SURVEY.md §3c).  The TPU-native
equivalent: one process per host joins a ``jax.distributed`` cluster; the
global device mesh then spans hosts, and the SAME shard_map step from
step.py runs unmodified — XLA routes the register merges over ICI within a
pod slice and over DCN between hosts.

Because every collective here reduces *small replicated registers* (not
the batch), the DCN hop costs one latency per chunk, not bandwidth —
the design scales to multi-host exactly like per-pod.

This module is exercised single-host in CI (the fake-device mesh covers
the SPMD program); multi-host init itself needs a real cluster.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

from ..errors import ChipBindingError


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    heartbeat_timeout_seconds: int | None = None,
    initialization_timeout: int | None = None,
) -> None:
    """Join (or bootstrap) the multi-host cluster.

    With no arguments, relies on the environment (TPU pod metadata / the
    launcher's JAX_COORDINATOR_* variables), which is how TPU pods
    normally initialize.

    ``heartbeat_timeout_seconds`` bounds dead-peer detection: when a
    process dies mid-job, the coordinator declares it missing after this
    long and every surviving process's pending collective aborts with an
    error instead of hanging — the rebuilt analog of YARN failing a job
    whose task died (SURVEY.md §6 failure detection).  None keeps JAX's
    default (100s).

    ``initialization_timeout`` bounds cluster FORMATION: a member listed
    in a re-formation plan that dies before joining would otherwise hold
    everyone in initialize() for jax's 300 s default.

    A loopback coordinator means every process runs on this host; see
    :func:`check_one_chip_per_process`.
    """
    if coordinator_address and num_processes and is_loopback(
        coordinator_address.rpartition(":")[0]
    ):
        check_one_chip_per_process(
            num_processes, "jax.distributed processes behind a loopback "
            f"coordinator ({coordinator_address})",
        )
    # Cross-process collectives on the CPU backend (the fake-mesh test
    # idiom and any CPU-host deployment) need a CPU collectives library.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kw = {}
    if heartbeat_timeout_seconds is not None:
        kw["heartbeat_timeout_seconds"] = heartbeat_timeout_seconds
    if initialization_timeout is not None:
        kw["initialization_timeout"] = initialization_timeout
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kw,
    )


def is_loopback(host: str) -> bool:
    """True for a host name or address that only this machine answers."""
    host = host.strip("[]")
    return host == "localhost" or host.startswith("127.") or host == "::1"


def check_one_chip_per_process(n_processes: int, what: str) -> None:
    """Refuse ``n_processes`` JAX processes on one host unless they use the CPU.

    A process that starts the TPU backend takes every chip of its host,
    so a second process on the same host fails or hangs at start-up.
    Nothing in this repo binds one distinct chip to each process, so
    such a mode is refused by name.  The platform is read from
    ``JAX_PLATFORMS`` without starting a backend in this process, which
    may itself be the one about to spawn the others.
    """
    platform = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    if n_processes <= 1 or platform == "cpu":
        return
    raise ChipBindingError(
        f"{what}: {n_processes} processes on one host would each need a "
        f"chip of their own (JAX_PLATFORMS={platform or 'unset'}), and "
        "nothing binds one to each.  Run one process per host, use the "
        "in-process thread mode, or set JAX_PLATFORMS=cpu for a CPU "
        "rehearsal."
    )


def make_global_mesh(
    axis: str = "data", *, topology: str = "flat", dcn: int = 0
) -> Mesh:
    """The global mesh over every device of every host.

    ``topology="flat"`` (default): one data axis.  A flat axis is
    already correct for register merging — XLA decomposes the global
    psum/pmax into an ICI reduction per pod slice plus a DCN exchange
    between hosts on its own.

    ``topology="hybrid"``: the explicit two-level DCN x ICI mesh
    (SNIPPETS.md [2] ``create_hybrid_device_mesh`` idiom) — an outer
    ``dcn`` axis of ``dcn`` groups (0 = one per process/host) times an
    inner ICI axis; ``jax.devices()`` orders devices by process, so the
    row-major reshape puts each host's devices in one outer group
    exactly as ``create_hybrid_device_mesh`` would.  Batches shard over
    both axes and the register merges reduce over both; reports stay
    bit-identical to the flat mesh (parallel/mesh.py pins the law).
    This is the committed direction for growing world size past one
    host: the outer axis is where the autoscaler adds hosts.
    """
    from . import mesh as mesh_lib

    return mesh_lib.make_mesh(
        list(jax.devices()), axis, topology=topology, dcn=dcn
    )


def local_batch_slice(global_batch_size: int) -> tuple[int, int]:
    """This process's [start, stop) share of each global batch.

    The streaming driver on each host parses only its own slice of the
    input (the analog of HDFS input splits), then forms the global sharded
    array with jax.make_array_from_process_local_data.  Uniform sharding
    requires equal per-process slices, so the global batch size must
    divide evenly (pad_batch_size over the global mesh guarantees a
    device-count multiple; device counts are equal per host on TPU pods).
    """
    n = jax.process_count()
    if global_batch_size % n:
        raise ValueError(
            f"global batch size {global_batch_size} not divisible by "
            f"{n} processes; round it with parallel.mesh.pad_batch_size"
        )
    i = jax.process_index()
    per = global_batch_size // n
    return i * per, (i + 1) * per


def to_global(mesh: Mesh, local_np: np.ndarray, spec) -> jax.Array:
    """Assemble this process's local numpy block into a global jax.Array.

    ``spec`` is the global PartitionSpec; replicated leaves (``P()``) must
    hold identical data on every process (true for the analysis state and
    rule tensor, which every process computes from the same ruleset).
    """
    from jax.sharding import NamedSharding

    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), np.ascontiguousarray(local_np)
    )


def all_processes_have_data(has_data: bool) -> bool:
    """True while ANY process still has input (one tiny allgather).

    The chunk loop is a collective program: every process must invoke the
    jitted step the same number of times or the job deadlocks.  Processes
    whose input split ran dry keep stepping all-invalid batches until
    every split is exhausted — the register updates are weighted by the
    valid mask, so padding rounds change nothing.
    """
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(np.asarray([1 if has_data else 0]))
    return bool(np.asarray(flags).sum() > 0)


def value_across_processes(value: int) -> np.ndarray:
    """Every process's value, as a [process_count] array (tiny allgather)."""
    from jax.experimental import multihost_utils

    arr = np.asarray([int(value)], dtype=np.int64)
    return np.asarray(multihost_utils.process_allgather(arr)).reshape(-1)


def allgather_rows(rows: np.ndarray) -> np.ndarray:
    """Concatenate a small per-process [n_i, C] uint32 array across processes.

    ``process_allgather`` needs equal shapes, so row counts gather first
    and each contribution pads to the max before the data gather.  Meant
    for tiny side tables (e.g. v6 talker digest->address rows), not bulk
    data.
    """
    from jax.experimental import multihost_utils

    rows = np.ascontiguousarray(rows, dtype=np.uint32)
    counts = value_across_processes(rows.shape[0])
    m = int(counts.max()) if counts.size else 0
    if m == 0:
        return rows.reshape(0, rows.shape[1] if rows.ndim == 2 else 0)
    padded = np.zeros((m, rows.shape[1]), dtype=np.uint32)
    padded[: rows.shape[0]] = rows
    gathered = np.asarray(multihost_utils.process_allgather(padded))
    if gathered.ndim == 2:
        # some jax versions return the single-process gather UNSTACKED
        # (no leading process axis); normalize to [n_procs, m, C]
        gathered = gathered[None]
    return np.concatenate(
        [gathered[p, : int(counts[p])] for p in range(gathered.shape[0])]
    )


def sum_across_processes(values: dict[str, int]) -> dict[str, int]:
    """Aggregate per-process counters (parsed/skipped/lines) for totals."""
    from jax.experimental import multihost_utils

    keys = sorted(values)
    arr = np.asarray([int(values[k]) for k in keys], dtype=np.int64)
    summed = np.asarray(multihost_utils.process_allgather(arr)).reshape(
        jax.process_count(), len(keys)
    ).sum(axis=0)
    return {k: int(v) for k, v in zip(keys, summed)}


# ---------------------------------------------------------------------------
# Host-tier epoch wire format (runtime/distserve.py, DESIGN §22).
#
# The distributed serve deployment realizes the hybrid mesh's outer
# ("dcn") axis HOST-SIDE: each host accumulates a window into its own
# register planes, and at rotation ships the epoch to rank 0 over a
# control-plane socket (loopback TCP between co-located processes, DCN
# between machines).  A jax.distributed collective would be the obvious
# alternative — but a dead host poisons every surviving peer's pending
# collective, and the serve contract is the opposite: survivors keep
# publishing (degraded, typed WindowIncomplete) when a whole host dies.
# Host-side merge under the proven _merge_tail laws (add64/add32/max)
# keeps the published reports bit-identical to the collective reduction
# AND to a single-host replay of the union of delivered lines, while a
# host's death costs a timeout, never a hang.
# ---------------------------------------------------------------------------


def pack_epoch_payload(
    arrays: dict[str, np.ndarray], extra: dict
) -> bytes:
    """One host's rotated window -> self-delimiting CRC'd wire bytes.

    Layout: ``RAEP1`` magic, u32 JSON length, u32 npz length, u32
    CRC32 over both bodies, JSON (meta/tables/accounting), npz (the
    register arrays).  The CRC catches a torn or interleaved write on
    the host-tier socket the way the WAL and checkpoint planes catch
    torn files — a corrupt epoch must be a typed refusal at the merge
    tier, never silently-wrong published counters.
    """
    import io
    import json as _json
    import struct
    import zlib

    buf = io.BytesIO()
    np.savez(buf, **{k: np.ascontiguousarray(v) for k, v in arrays.items()})
    npz = buf.getvalue()
    meta = _json.dumps(extra, separators=(",", ":")).encode("utf-8")
    crc = zlib.crc32(meta)
    crc = zlib.crc32(npz, crc) & 0xFFFFFFFF
    return (
        b"RAEP1"
        + struct.pack("<III", len(meta), len(npz), crc)
        + meta
        + npz
    )


def unpack_epoch_payload(payload: bytes) -> tuple[dict[str, np.ndarray], dict]:
    """Inverse of :func:`pack_epoch_payload`; typed on any corruption."""
    import io
    import json as _json
    import struct
    import zlib

    from ..errors import AnalysisError

    if len(payload) < 17 or payload[:5] != b"RAEP1":
        raise AnalysisError(
            "host-tier epoch payload lacks the RAEP1 magic (torn frame "
            "or a foreign writer on the merge socket)"
        )
    n_meta, n_npz, crc = struct.unpack("<III", payload[5:17])
    body = payload[17:]
    if len(body) != n_meta + n_npz:
        raise AnalysisError(
            f"host-tier epoch payload truncated: header promises "
            f"{n_meta + n_npz} body bytes, got {len(body)}"
        )
    meta, npz = body[:n_meta], body[n_meta:]
    got = zlib.crc32(npz, zlib.crc32(meta)) & 0xFFFFFFFF
    if got != crc:
        raise AnalysisError(
            f"host-tier epoch payload CRC mismatch (want {crc:#x}, got "
            f"{got:#x}): refusing to merge a corrupt epoch"
        )
    with np.load(io.BytesIO(npz)) as z:
        arrays = {k: z[k] for k in z.files}
    return arrays, _json.loads(meta.decode("utf-8"))
