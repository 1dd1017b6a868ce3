"""The versioned on-disk solver cache: schedules, plans, δ-model, executables.

Layout (one namespace directory per solver content key, see
:mod:`repro.persist.keys`)::

    <cache_dir>/v<CACHE_FORMAT>/<namespace[:16]>/
        meta.json             human-readable key anatomy (debugging only)
        sched_d<δ>.npz        DeviceSchedule stripe arrays
        plan_d<δ>_D<D>.npz    FrontierPlan halo indices per mesh width
        exec_<digest>.bin     jax.export blob per (key, arg shapes/dtypes)
        delta_model.json      fitted DeltaModel + the δ* currently served
        observations.jsonl    (δ, rounds, time) from production EngineResults

Every write is atomic (tmp file + ``os.replace``) so a killed process never
leaves a truncated entry; every load is wrapped so a corrupt, partial, or
foreign entry is a **miss** (the caller rebuilds cold and overwrites), never
an exception on the solve path and never a wrong answer.  Entries are safe to
share between hosts with the same jax/numpy versions; the executable blobs
additionally assume the same platform (they are skipped, not trusted, when
they fail to deserialize).
"""

from __future__ import annotations

import errno
import hashlib
import io
import itertools
import json
import os
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delta_model import DeltaModel
from repro.core.engine import DeviceSchedule
from repro.dist.compat import export_deserialize, export_serialize
from repro.ft.inject import fire
from repro.persist.keys import (
    CACHE_FORMAT,
    env_fingerprint,
    graph_fingerprint,
    problem_fingerprint,
    solver_namespace,
)

__all__ = ["SolverCache"]

# tmp names are unique per (pid, thread, write): two *threads* of one process
# used to share a pid-only tmp name, so one thread's write_bytes could land in
# a file the other was about to os.replace — a torn entry under a valid name.
_TMP_COUNTER = itertools.count()
# serializes the observation log's check-compact-append sequence per process
_OBS_LOCK = threading.Lock()


def _atomic_write_bytes(path: Path, data: bytes) -> None:
    """Crash- and race-safe publish: unique tmp + fsync + atomic replace.

    Concurrent writers of one key are last-writer-wins: each stages into its
    own tmp file and publishes with a single ``os.replace``, so a concurrent
    reader sees the old complete entry or the new complete entry, never a
    mix; the fsync before replace means the rename can never promote
    still-unwritten bytes after a crash.
    """
    kind = fire("persist.write", key=path.name)
    if kind == "eio":
        raise OSError(errno.EIO, f"injected EIO writing {path.name}")
    if kind == "corrupt":  # bit-flip the head: loaders must treat it as a miss
        data = bytes(b ^ 0xFF for b in data[:64]) + data[64:]
    if kind == "torn":  # a kill mid-write: only a prefix reaches the tmp file
        data = data[: max(1, len(data) // 2)]
    tmp = path.with_name(
        f"{path.name}.tmp-{os.getpid()}-{threading.get_ident()}-{next(_TMP_COUNTER)}"
    )
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _read_fault(path: Path) -> None:
    """Chaos hook for the load path; called inside each loader's try block so
    an injected read fault surfaces as a cache miss, never an exception."""
    kind = fire("persist.read", key=path.name)
    if kind is not None:
        raise OSError(errno.EIO, f"injected {kind} fault reading {path.name}")


def _save_npz(path: Path, arrays: dict) -> None:
    """Best-effort atomic ``np.savez``; a full disk degrades, never raises."""
    try:
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        _atomic_write_bytes(path, buf.getvalue())
    except OSError:  # pragma: no cover - best-effort persistence
        pass


class SolverCache:
    """One solver's persisted entries under a content-derived namespace.

    Construct via :meth:`for_solver`; all ``load_*`` methods return ``None``
    on any miss/mismatch/corruption, all ``save_*`` methods are atomic and
    best-effort (a full disk degrades to a process-local cache, it does not
    break solving).
    """

    def __init__(self, root, namespace: str, meta: dict | None = None):
        self.root = Path(root)
        self.namespace = namespace
        self.dir = self.root / f"v{CACHE_FORMAT}" / namespace[:16]
        try:
            self.dir.mkdir(parents=True, exist_ok=True)
        except OSError:
            # read-only mount / full disk: every load below misses and every
            # save is a no-op — the solver degrades to its process-local cache
            return
        meta_path = self.dir / "meta.json"
        if meta is not None and not meta_path.exists():
            try:
                _atomic_write_bytes(
                    meta_path, json.dumps(meta, indent=1).encode()
                )
            except OSError:  # pragma: no cover - best-effort debug aid
                pass

    @classmethod
    def for_solver(
        cls,
        root,
        graph,
        problem,
        row_update_q,
        q_template,
        n_workers: int,
        partition_method: str,
        min_chunk: int,
        tol: float,
        max_rounds: int,
    ) -> "SolverCache":
        """The namespace for one ``(graph, problem, shape knobs)`` binding.

        ``graph`` must be the *schedule* graph (edge-value overrides applied)
        so e.g. CC's zeroed weights and SSSP's lengths hash differently;
        ``tol``/``max_rounds`` are the solver's effective values.
        """
        ns = solver_namespace(
            graph, problem, row_update_q, q_template,
            n_workers, partition_method, min_chunk, tol, max_rounds,
        )
        meta = {
            "env": env_fingerprint(),
            "graph": graph.name,
            "graph_fingerprint": graph_fingerprint(graph)[:16],
            "problem": problem.name,
            "problem_fingerprint": problem_fingerprint(
                problem, row_update_q, problem.semiring, q_template
            )[:16],
            "n_workers": int(n_workers),
            "partition_method": partition_method,
            "min_chunk": int(min_chunk),
            "tol": float(tol),
            "max_rounds": int(max_rounds),
        }
        return cls(root, ns, meta)

    # ------------------------------------------------------------------ #
    # stripe schedules
    # ------------------------------------------------------------------ #
    def _sched_path(self, delta: int) -> Path:
        return self.dir / f"sched_d{int(delta)}.npz"

    def save_schedule(self, sched: DeviceSchedule) -> None:
        _save_npz(self._sched_path(sched.delta), sched.to_host_arrays())

    def load_schedule(self, delta: int, put=jnp.asarray) -> DeviceSchedule | None:
        """The persisted schedule for ``delta``, placed by ``put``, or None."""
        path = self._sched_path(delta)
        try:
            _read_fault(path)
            with np.load(path, allow_pickle=False) as arrays:
                sched = DeviceSchedule.from_host_arrays(arrays, put)
            if sched.delta != int(delta):
                return None
            return sched
        except Exception:
            return None

    # ------------------------------------------------------------------ #
    # shared per-worker stripes (content-addressed, cross-namespace)
    # ------------------------------------------------------------------ #
    # Stripes live OUTSIDE the namespace on purpose: a graph mutation changes
    # the namespace (it hashes the whole graph), so per-namespace stripe
    # storage would never be warm after an update.  The digest alone proves
    # reusability (it hashes the block's own edge content + env), making the
    # shared directory safe across graphs, problems, and solvers.

    def _stripe_path(self, digest: str) -> Path:
        return self.root / f"v{CACHE_FORMAT}" / "stripes" / f"{digest[:24]}.npz"

    def save_stripe(self, digest: str, stripe: dict) -> None:
        """Persist one worker stripe under its content digest (atomic)."""
        path = self._stripe_path(digest)
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
        except OSError:  # pragma: no cover - best-effort persistence
            return
        _save_npz(path, stripe)

    def load_stripe(self, digest: str) -> dict | None:
        """The stripe dict for ``digest`` or ``None`` (corruption ⇒ miss)."""
        try:
            _read_fault(self._stripe_path(digest))
            with np.load(self._stripe_path(digest), allow_pickle=False) as arrays:
                out = {k: np.asarray(arrays[k]) for k in arrays.files}
            if not {"src", "val", "dst_local", "rows", "row_last"} <= out.keys():
                return None
            return out
        except Exception:
            return None

    # ------------------------------------------------------------------ #
    # shared frontier-plan shard pieces (content-addressed, cross-namespace)
    # ------------------------------------------------------------------ #
    def _plan_shard_path(self, digest: str) -> Path:
        return self.root / f"v{CACHE_FORMAT}" / "planshards" / f"{digest[:24]}.npz"

    def save_plan_shard(self, digest: str, piece: dict) -> None:
        path = self._plan_shard_path(digest)
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
        except OSError:  # pragma: no cover - best-effort persistence
            return
        _save_npz(path, piece)

    def load_plan_shard(self, digest: str) -> dict | None:
        try:
            _read_fault(self._plan_shard_path(digest))
            with np.load(self._plan_shard_path(digest), allow_pickle=False) as arrays:
                out = {k: np.asarray(arrays[k]) for k in arrays.files}
            if not {"halo", "src_loc", "rows_loc"} <= out.keys():
                return None
            return out
        except Exception:
            return None

    # ------------------------------------------------------------------ #
    # frontier halo plans
    # ------------------------------------------------------------------ #
    def _plan_path(self, delta: int, D: int) -> Path:
        return self.dir / f"plan_d{int(delta)}_D{int(D)}.npz"

    def save_plan(self, plan) -> None:
        _save_npz(self._plan_path(plan.delta, plan.D), plan.to_host_arrays())

    def load_plan(self, delta: int, D: int):
        from repro.dist.engine_sharded import FrontierPlan

        try:
            _read_fault(self._plan_path(delta, D))
            with np.load(self._plan_path(delta, D), allow_pickle=False) as arrays:
                plan = FrontierPlan.from_host_arrays(arrays)
            if plan.delta != int(delta) or plan.D != int(D):
                return None
            return plan
        except Exception:
            return None

    # ------------------------------------------------------------------ #
    # compiled round / loop executables (jax.export blobs)
    # ------------------------------------------------------------------ #
    @staticmethod
    def _exec_digest(key: tuple, args) -> str:
        h = hashlib.sha256(repr(key).encode())
        for leaf in jax.tree_util.tree_leaves(tuple(args)):
            # .dtype directly: np.asarray would copy device buffers to host
            # just to read a dtype string
            dt = leaf.dtype if hasattr(leaf, "dtype") else np.asarray(leaf).dtype
            h.update(f"{np.shape(leaf)}:{dt};".encode())
        return h.hexdigest()[:24]

    def _exec_path(self, key: tuple, args) -> Path:
        return self.dir / f"exec_{self._exec_digest(key, args)}.bin"

    def save_executable(self, key: tuple, fn, args) -> bool:
        """Export + persist ``fn`` for ``args``' shapes; False if not portable."""
        blob = export_serialize(fn, args)
        if blob is None:
            return False
        try:
            _atomic_write_bytes(self._exec_path(key, args), blob)
            return True
        except OSError:  # pragma: no cover - best-effort persistence
            return False

    def load_executable(self, key: tuple, args):
        """The deserialized jit-able callable for ``(key, args)``, or ``None``.

        The callable replays the exported StableHLO — compiling it never
        re-traces the Python that originally built the round, which is what
        keeps a warm process at zero retraces.
        """
        path = self._exec_path(key, args)
        try:
            _read_fault(path)
            blob = path.read_bytes()
        except OSError:
            return None
        return export_deserialize(blob)

    # ------------------------------------------------------------------ #
    # δ-model + production observations
    # ------------------------------------------------------------------ #
    def save_delta_model(
        self, model: DeltaModel, best_delta: int, regime: str = "cold"
    ) -> None:
        """Persist one regime's model, preserving the other regime's section.

        The cold regime keeps the legacy top-level keys (old caches stay
        readable); any other regime writes ``<regime>_model`` /
        ``<regime>_best_delta`` alongside.
        """
        path = self.dir / "delta_model.json"
        try:
            payload = json.loads(path.read_text())
        except Exception:
            payload = {}
        if regime == "cold":
            payload["best_delta"] = int(best_delta)
            payload["model"] = model.to_dict()
        else:
            payload[f"{regime}_best_delta"] = int(best_delta)
            payload[f"{regime}_model"] = model.to_dict()
        try:
            _atomic_write_bytes(path, json.dumps(payload, indent=1).encode())
        except OSError:  # pragma: no cover - best-effort persistence
            pass

    def load_delta_model(
        self, regime: str = "cold"
    ) -> tuple[DeltaModel, int] | None:
        """``(model, best_delta)`` for ``regime`` as last fitted, or ``None``."""
        try:
            _read_fault(self.dir / "delta_model.json")
            payload = json.loads((self.dir / "delta_model.json").read_text())
            if regime == "cold":
                model, best = payload["model"], payload["best_delta"]
            else:
                model = payload[f"{regime}_model"]
                best = payload[f"{regime}_best_delta"]
            return DeltaModel.from_dict(model), int(best)
        except Exception:
            return None

    # Compact the observation log once it exceeds this, keeping the newest
    # rows — bounds both the directory and reprobe_delta's refit cost for
    # arbitrarily long-lived services.
    _OBS_MAX_BYTES = 1 << 20
    _OBS_KEEP_ROWS = 4096

    def record_observation(
        self,
        delta: int,
        rounds: int,
        total_time_s: float,
        backend: str,
        kind: str = "solve",
        regime: str = "cold",
    ) -> None:
        """Append one production ``(δ, rounds, time)`` datapoint (JSONL).

        ``regime`` separates cold solves from incremental warm restarts —
        incremental round counts are far lower for the same δ, so mixing the
        regimes in one fit would bias both curves.
        """
        row = {
            "delta": int(delta),
            "rounds": int(rounds),
            "total_time_s": float(total_time_s),
            "backend": backend,
            "kind": kind,
            "regime": regime,
        }
        path = self.dir / "observations.jsonl"
        try:
            # the check-compact-append sequence is not atomic; the lock keeps
            # two in-process writers from interleaving a compaction with an
            # append (cross-process appends remain safe: O_APPEND semantics)
            with _OBS_LOCK:
                if path.exists() and path.stat().st_size > self._OBS_MAX_BYTES:
                    tail = self.load_observations()[-self._OBS_KEEP_ROWS :]
                    _atomic_write_bytes(
                        path, "".join(json.dumps(r) + "\n" for r in tail).encode()
                    )
                with open(path, "a") as f:
                    f.write(json.dumps(row) + "\n")
        except OSError:  # pragma: no cover - best-effort persistence
            pass

    def load_observations(self) -> list[dict]:
        """All readable observation rows (a truncated tail line is skipped)."""
        out = []
        try:
            text = (self.dir / "observations.jsonl").read_text()
        except OSError:
            return out
        for line in text.splitlines():
            try:
                row = json.loads(line)
                out.append(
                    {
                        "delta": int(row["delta"]),
                        "rounds": int(row["rounds"]),
                        "total_time_s": float(row["total_time_s"]),
                        "backend": row.get("backend", "?"),
                        "kind": row.get("kind", "solve"),
                        "regime": row.get("regime", "cold"),
                    }
                )
            except (ValueError, KeyError, TypeError):
                continue  # partial write from a killed process: skip the line
        return out
