"""Multi-process host communication for the distributed streaming build.

The paper's Alg. 2 runs construction per MPI rank with two communication
primitives: an all-reduce over small dense summaries (k-means centers and
counts, radii, loss/grad scalars) and a point-to-point candidate/member
exchange. This module provides both on top of ``jax.distributed``:

* **collectives** — a one-device-per-host mesh with the *gloo* CPU
  collective backend; ``allreduce`` runs a cached jitted
  ``shard_map``-``psum``/``pmax`` so every host gets the identical
  reduced bytes (which is what keeps optimizer states replicated without
  a broadcast);
* **point-to-point** — the ``jax.distributed`` coordination service's
  key-value store moves ``npz``-serialized array payloads between host
  pairs (``exchange``). The KV store is a rendezvous service, not an
  interconnect — fine for construction metadata and the bounded halo
  rows it carries here; the steady-state inner loop communicates ONLY
  through ``allreduce`` (O(1) scalars per chunk per step, the Alg. 1
  contract).

``LoopbackComm`` implements the same interface degenerately for one
process; every ``comm=``-aware code path can therefore be exercised (and
is pinned bitwise against the single-process path) without spawning
processes. See docs/streaming.md "multi-host construction".
"""
from __future__ import annotations

import base64
import functools
import io
import os

import numpy as np

# Environment contract for launched worker processes (repro.launch.fit_gp
# spawns local ranks with these; a real cluster can export them instead).
ENV_RANK = "REPRO_DIST_RANK"
ENV_NPROCS = "REPRO_DIST_NPROCS"
ENV_COORD = "REPRO_DIST_COORD"

_KV_PART_BYTES = 2 << 20  # KV values are chunked to stay rendezvous-friendly


def require_cpu_ranks() -> None:
    """Refuse to launch rank processes on an accelerator host.

    The multi-process mode runs one JAX process per rank with CPU (gloo)
    collectives. An accelerator chip belongs to one process at a time:
    the launching process holds it once JAX is initialized, and a rank
    that then asks for it fails or hangs. One process drives all of a
    host's chips through an in-process mesh instead."""
    import jax

    platform = jax.default_backend()
    if platform != "cpu":
        raise SystemExit(
            "--distributed-hosts runs one JAX process per rank on CPU "
            "collectives, but "
            f"this host's {platform} chips belong to one process. Use "
            "--workers K: one process drives all local chips through an "
            "in-process mesh.")


def _flat(key: str) -> str:
    """Keep KV keys slash-free: the coordination service treats ``/`` as
    a directory separator (``key_value_dir_get``), so flat keys avoid any
    ambiguity with the namespace GC."""
    return key.replace("/", ".")


def partition_blocks(n_blocks: int, size: int) -> list:
    """Contiguous ``[lo, hi)`` block spans per rank (``np.array_split``
    semantics: the first ``n_blocks % size`` ranks carry one extra).
    Every rank computes the identical table from the identical packed
    chunk, so block ownership in the multi-host predict path
    (``predict_sbv(multihost=)``) needs zero coordination."""
    base, extra = divmod(int(n_blocks), int(size))
    spans, lo = [], 0
    for r in range(int(size)):
        hi = lo + base + (1 if r < extra else 0)
        spans.append((lo, hi))
        lo = hi
    return spans


class LoopbackComm:
    """Single-process implementation of the host-comm interface.

    ``allreduce`` is the identity (so it perturbs no floats — the
    ``multihost=`` fit path with a LoopbackComm is bitwise the plain
    streaming fit) and ``exchange`` hands each payload straight back.
    """

    rank = 0
    size = 1

    def allreduce(self, vec, op: str = "sum") -> np.ndarray:
        return np.asarray(vec, dtype=np.float64).copy()

    def allreduce_scalar(self, v: float, op: str = "sum") -> float:
        return float(v)

    def exchange(self, payloads: dict) -> dict:
        out = {}
        if 0 in payloads:
            out[0] = {k: np.asarray(v) for k, v in payloads[0].items()}
        return out

    def barrier(self, tag: str = "") -> None:
        pass

    def shutdown(self) -> None:
        pass


class MultihostContext:
    """Host comm over an initialized ``jax.distributed`` runtime."""

    def __init__(self, rank: int, size: int, client, mesh):
        self.rank = int(rank)
        self.size = int(size)
        self._client = client
        self._mesh = mesh
        self._seq = 0
        self.bytes_sent = 0
        self.bytes_recv = 0
        self.timeout_ms = 600_000

    # -- construction --------------------------------------------------

    @classmethod
    def connect(cls, coordinator: str, num_processes: int,
                process_id: int) -> "MultihostContext":
        """Initialize ``jax.distributed`` (gloo CPU collectives) and build
        the one-device-per-host mesh. Must run before any other jax use
        in the process."""
        import jax

        # The CPU backend refuses multi-process computations unless the
        # gloo collective implementation is selected BEFORE initialize.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=int(num_processes),
                                   process_id=int(process_id))
        from jax._src.distributed import global_state
        from jax.sharding import Mesh

        devices = np.asarray(jax.devices())
        if devices.flat[0].platform != "cpu":
            raise RuntimeError(
                "MultihostContext runs CPU (gloo) collectives with one CPU "
                f"device per process; this process sees {devices.size} "
                f"{devices.flat[0].platform} devices. Drive accelerator "
                "chips from one process (--workers K).")
        if devices.size != int(num_processes):
            raise RuntimeError(
                f"expected one device per process, got {devices.size} devices "
                f"for {num_processes} processes")
        mesh = Mesh(devices, ("hosts",))
        return cls(process_id, num_processes, global_state.client, mesh)

    @classmethod
    def from_env(cls) -> "MultihostContext | None":
        """Connect from the ``REPRO_DIST_*`` environment, or None."""
        if ENV_RANK not in os.environ:
            return None
        return cls.connect(os.environ[ENV_COORD],
                           int(os.environ[ENV_NPROCS]),
                           int(os.environ[ENV_RANK]))

    # -- collectives ----------------------------------------------------

    @functools.lru_cache(maxsize=32)
    def _allreduce_fn(self, length: int, op: str):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def local(x):  # x: this host's (1, length) shard
            v = jnp.squeeze(x, axis=0)
            if op == "sum":
                return jax.lax.psum(v, "hosts")
            return jax.lax.pmax(v, "hosts")

        return jax.jit(jax.shard_map(local, mesh=self._mesh,
                                     in_specs=(P("hosts"),), out_specs=P()))

    def allreduce(self, vec, op: str = "sum") -> np.ndarray:
        """Element-wise sum/max/min across hosts of a float64 vector.

        The reduced result is identical bytes on every host (a collective
        allreduce agrees on one result), which is what keeps replicated
        state — centers, optimizer moments, parameters — in lockstep
        without any broadcast step.
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        arr = np.asarray(vec, dtype=np.float64)
        flat = arr.ravel()
        if flat.size == 0:
            return arr.copy()
        neg = op == "min"
        local = (-flat if neg else flat)[None, :]
        sharding = NamedSharding(self._mesh, P("hosts"))
        g = jax.make_array_from_process_local_data(sharding, local)
        out = np.asarray(self._allreduce_fn(flat.size, "max" if neg else op)(g))
        if neg:
            out = -out
        return out.reshape(arr.shape)

    def allreduce_scalar(self, v: float, op: str = "sum") -> float:
        return float(self.allreduce(np.asarray([v], dtype=np.float64), op)[0])

    # -- point-to-point -------------------------------------------------

    # Payloads go through the *string* KV API with base64 values: the
    # ``*_bytes`` getter binding in current jaxlib segfaults
    # intermittently (races in its future-to-bytes conversion), while the
    # string path is the one jax itself exercises for device coordination.
    # Raw bytes are chunked BEFORE encoding so each stored value stays
    # near _KV_PART_BYTES.

    def _kv_put(self, key: str, blob: bytes) -> None:
        n_parts = -(-len(blob) // _KV_PART_BYTES)
        for i in range(n_parts):
            part = blob[i * _KV_PART_BYTES:(i + 1) * _KV_PART_BYTES]
            self._client.key_value_set(
                _flat(f"{key}.p{i}"), base64.b64encode(part).decode("ascii"))
        self._client.key_value_set(_flat(f"{key}.meta"), str(n_parts))

    def _kv_get(self, key: str) -> bytes:
        n_parts = int(self._client.blocking_key_value_get(
            _flat(f"{key}.meta"), self.timeout_ms))
        parts = [base64.b64decode(self._client.blocking_key_value_get(
            _flat(f"{key}.p{i}"), self.timeout_ms)) for i in range(n_parts)]
        return b"".join(parts)

    def _kv_delete(self, key: str) -> None:
        try:
            self._client.key_value_delete(key)
        except Exception:
            pass  # best-effort GC; stale keys are seq-namespaced anyway

    @staticmethod
    def _pack(payload: dict) -> bytes:
        buf = io.BytesIO()
        np.savez(buf, **{k: np.ascontiguousarray(v)
                         for k, v in payload.items()})
        return buf.getvalue()

    @staticmethod
    def _unpack(blob: bytes) -> dict:
        with np.load(io.BytesIO(blob)) as z:
            return {k: z[k] for k in z.files}

    def exchange(self, payloads: dict) -> dict:
        """All-to-all of ``{dest_rank: {name: array}}`` payload dicts.

        COLLECTIVE: every host must call it the same number of times
        (missing destinations send implicit empty payloads). Returns
        ``{src_rank: {name: array}}`` with an entry for every peer that
        sent a non-empty payload (plus self, if addressed). Keys are
        sequence-numbered and garbage-collected after a barrier, so the
        coordination service holds at most one round in flight.
        """
        seq = self._seq
        self._seq += 1
        out = {}
        mine = payloads.get(self.rank)
        if mine is not None:
            out[self.rank] = {k: np.asarray(v) for k, v in mine.items()}
        sent_keys = []
        for dst in range(self.size):
            if dst == self.rank:
                continue
            payload = payloads.get(dst)
            blob = self._pack(payload) if payload else b""
            key = f"repro.x{seq}.{self.rank}to{dst}"
            self._kv_put(key, blob)
            sent_keys.append(key)
            self.bytes_sent += len(blob)
        for src in range(self.size):
            if src == self.rank:
                continue
            blob = self._kv_get(f"repro.x{seq}.{src}to{self.rank}")
            self.bytes_recv += len(blob)
            if blob:
                out[src] = self._unpack(blob)
        self.barrier(f"x{seq}")
        for key in sent_keys:
            self._kv_delete(key)
        return out

    def barrier(self, tag: str = "") -> None:
        self._client.wait_at_barrier(_flat(f"repro.bar.{tag}"), self.timeout_ms)

    def shutdown(self) -> None:
        import jax

        jax.distributed.shutdown()
