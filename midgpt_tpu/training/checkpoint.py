"""Async Orbax checkpointing with a *named* state tree + integrity manifests.

Upgrades over the reference, which saves bare `tree_leaves` tuples
(reference train.py:215) so restore requires rebuilding the exact tree
structure in code (reference sample.py:111-137 reconstructs the whole
optimizer chain just to get a skeleton):

  * state is a named dict {"params": ..., "opt_state": ...} serialized by
    key path — robust to incidental structure changes and readable by tools;
  * restore is sharding-aware: each host reads only its shards, directly
    into the live arrays' shardings (same property as reference
    train.py:179-187);
  * saves are async (training continues during the TensorStore write), with
    a final barrier on close (reference train.py:224-225).

Fault tolerance (docs/ROBUSTNESS.md):

  * **Write retry.** The synchronous part of a save (queueing the
    TensorStore write) retries `write_retries` times with exponential
    backoff before raising CheckpointWriteError — a transient filesystem
    hiccup must not kill a run that has hours of state in memory. Disk
    exhaustion (the `ckpt_enospc` fault: ENOSPC after partial bytes land)
    rides the same schedule; the partial, un-manifested step directory is
    swept before each retry and on budget exhaustion, so it is never
    visible to `latest_verified_step` and never shadows the last good
    checkpoint.
  * **Checksum manifests.** After an async save lands, a per-file sha256
    manifest is committed (atomic rename) into the step directory. A step
    is *verified* iff every file matches its manifest. `restore` re-verifies
    and raises CheckpointCorruptError with a per-file diagnosis; resume uses
    `latest_verified_step`, so a checkpoint truncated by a mid-save kill is
    skipped, never half-restored. Manifests are local-path only; gs://
    rundirs keep the plain orbax behavior.
  * **Verified-only GC** (local paths). Orbax's own max_to_keep would delete
    the previous checkpoint the moment a new save finalizes — before anyone
    checked the new one is readable. Here GC is explicit: a step is deleted
    only once `max_to_keep` (default 2) NEWER verified steps exist, so a
    crash mid-save can never destroy the only good checkpoint.

Layout note: checkpoints are saved as named Composite items ("params",
"opt_state") plus a "format" JSON marker and a `midgpt_manifest.json`
integrity manifest; this is the framework's only supported layout — there
is no reader for other orbax layouts.
"""

from __future__ import annotations

import errno
import hashlib
import json
import os
import shutil
import typing as tp

import jax
import orbax.checkpoint as ocp

from midgpt_tpu.obs import flight_recorder
from midgpt_tpu.robustness import faults
from midgpt_tpu.robustness.backoff import retry_with_backoff
from midgpt_tpu.robustness.errors import (
    CheckpointCorruptError,
    CheckpointWriteError,
    SimulatedPreemption,
)

# Format marker saved alongside the state and verified at restore. Version
# history:
#   2 — wqkv rows were flat (3D, D) head-major interleaved; a flat stacked
#       checkpoint would restore into it without any shape error but every
#       head would read other heads' projection rows, so restore REFUSES
#       checkpoints without a matching marker.
#   3 — wqkv is (3, D, D) (models/gpt.py AttentionParams): shape-distinct
#       from both flat layouts, so cross-layout restores also fail loudly at
#       the orbax level; the marker remains the explicit, diagnosable gate.
#       tools/migrate_ckpt_v2_v3.py converts v2 checkpoints in place.
FORMAT = {"version": 3, "qkv_layout": "qkv3"}

MANIFEST_NAME = "midgpt_manifest.json"


def _abstract_like(tree: tp.Any) -> tp.Any:
    def conv(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.ShapeDtypeStruct) and x.sharding is None:
            # Orbax needs a concrete sharding to deserialize into; default to
            # replicated-on-default-device (the sampler's single-chip case).
            return jax.ShapeDtypeStruct(
                x.shape,
                x.dtype,
                sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0]),
            )
        return x

    return jax.tree.map(conv, tree)


def _hash_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def write_manifest(step_dir: str, step: int) -> None:
    """Commit a per-file sha256 manifest for a finalized step directory.

    The manifest is written to a temp file and os.replace'd into place, so a
    crash mid-write leaves the step *unverified* (no manifest), never
    half-verified. Exposed module-level so tools (migrate_ckpt_v2_v3) can
    stamp the checkpoints they produce."""
    files: tp.Dict[str, tp.Dict[str, tp.Any]] = {}
    for root, dirnames, names in os.walk(step_dir):
        dirnames.sort()
        for name in sorted(names):
            if name == MANIFEST_NAME:
                continue
            path = os.path.join(root, name)
            rel = os.path.relpath(path, step_dir)
            files[rel] = {"size": os.path.getsize(path), "sha256": _hash_file(path)}
    manifest = {"step": step, "format": FORMAT, "files": files}
    tmp = os.path.join(step_dir, MANIFEST_NAME + ".tmp")
    with open(tmp, "w") as fh:
        json.dump(manifest, fh, indent=1)
    os.replace(tmp, os.path.join(step_dir, MANIFEST_NAME))


def verify_manifest(step_dir: str) -> tp.List[str]:
    """Re-checksum a step directory against its manifest. Returns a list of
    human-readable problems — empty means verified."""
    mpath = os.path.join(step_dir, MANIFEST_NAME)
    if not os.path.exists(mpath):
        return [f"no {MANIFEST_NAME} in {step_dir} (save never completed?)"]
    try:
        with open(mpath) as fh:
            manifest = json.load(fh)
    except (OSError, ValueError) as e:
        return [f"unreadable manifest {mpath}: {e}"]
    problems: tp.List[str] = []
    for rel, rec in manifest.get("files", {}).items():
        path = os.path.join(step_dir, rel)
        if not os.path.exists(path):
            problems.append(f"missing item file: {rel}")
            continue
        size = os.path.getsize(path)
        if size != rec["size"]:
            problems.append(
                f"truncated item file: {rel} ({size} bytes, manifest says "
                f"{rec['size']})"
            )
            continue
        if _hash_file(path) != rec["sha256"]:
            problems.append(f"checksum mismatch: {rel}")
    return problems


class CheckpointManager:
    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 2,
        save_interval_steps: int = 1000,
        write_retries: int = 3,
        retry_backoff_sec: float = 0.5,
    ):
        self._local = not directory.startswith("gs://")
        if self._local:
            directory = os.path.abspath(directory)  # TensorStore requires absolute
        self._dir = directory
        options = ocp.CheckpointManagerOptions(
            # Local paths: GC is ours (verified-only, module docstring); on
            # gs:// there are no manifests, so keep orbax's rolling delete.
            max_to_keep=None if self._local else max_to_keep,
            save_interval_steps=save_interval_steps,
            enable_async_checkpointing=True,
        )
        self._mngr = ocp.CheckpointManager(directory, options=options)
        self.max_to_keep = max_to_keep
        self.write_retries = max(1, write_retries)
        self.retry_backoff_sec = retry_backoff_sec
        # Step whose async save has been queued but whose manifest is not
        # yet committed; finalized at the next save/wait/restore/close.
        self._pending: tp.Optional[int] = None

    # -- step inventory -------------------------------------------------

    def all_steps(self) -> tp.List[int]:
        return sorted(self._mngr.all_steps())

    def latest_step(self) -> tp.Optional[int]:
        return self._mngr.latest_step()

    def _step_dir(self, step: int) -> tp.Optional[str]:
        if not self._local:
            return None
        direct = os.path.join(self._dir, str(step))
        if os.path.isdir(direct):
            return direct
        if os.path.isdir(self._dir):
            # Tolerate prefixed step names (orbax step_name_format variants).
            for name in os.listdir(self._dir):
                tail = name.rsplit("_", 1)[-1]
                if tail.isdigit() and int(tail) == step:
                    return os.path.join(self._dir, name)
        return None

    def _has_manifest(self, step: int) -> bool:
        d = self._step_dir(step)
        return d is not None and os.path.exists(os.path.join(d, MANIFEST_NAME))

    def verify(self, step: int) -> tp.List[str]:
        """Problems with the step's integrity; [] means verified."""
        d = self._step_dir(step)
        if d is None:
            return [f"step {step} has no directory under {self._dir}"]
        return verify_manifest(d)

    def is_verified(self, step: int) -> bool:
        return self._local and not self.verify(step)

    def verified_steps(self) -> tp.List[int]:
        return [s for s in self.all_steps() if self.is_verified(s)]

    def weights_version(self, step: int) -> tp.Optional[str]:
        """'<step>:<sha12>' identity of a step's committed manifest — the
        value serving surfaces as `weights_version` on stats() so every
        round is attributable to exactly one verified
        checkpoint (sampling/ops.py hot-swap; "inline" means params were
        passed directly). Hashing the manifest FILE (which already records
        per-item sha256s) gives a stable content identity without
        re-hashing tensor bytes. None when the step has no manifest."""
        d = self._step_dir(step)
        if d is None:
            return None
        path = os.path.join(d, MANIFEST_NAME)
        if not os.path.exists(path):
            return None
        with open(path, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()
        return f"{step}:{digest[:12]}"

    def latest_verified_step(self) -> tp.Optional[int]:
        """Newest step whose manifest verifies — the only safe resume point.

        Directories with no manifests at all (pre-manifest runs, gs://) fall
        back to orbax's latest step; a MIXED directory trusts only verified
        steps, so a save truncated by a mid-save kill is skipped rather than
        resumed into."""
        self.wait()
        steps = self.all_steps()
        verified = [s for s in steps if self.is_verified(s)]
        if verified:
            return verified[-1]
        if steps and not any(self._has_manifest(s) for s in steps):
            return steps[-1]
        return None

    # -- save -----------------------------------------------------------

    def should_save(self, step: int) -> bool:
        """Would a non-forced save at `step` actually persist? Lets the train
        loop pay its pre-save health sync only on real save steps."""
        return bool(self._mngr.should_save(step))

    def save(self, step: int, state: tp.Dict[str, tp.Any], *, force: bool = False) -> bool:
        """Queue an async save of named items (e.g. {"params": ..., "opt_state": ...});
        the manager filters by save_interval_steps unless `force` (used for the
        final step of a run and emergency preemption saves).

        The synchronous part (queueing the write) retries with exponential
        backoff; the async part is verified and manifest-stamped at the next
        barrier (`wait`/next `save`/`close`)."""
        if not force and not self._mngr.should_save(step):
            return False
        self._finalize_pending()
        if step in self._mngr.all_steps() and not self.is_verified(step):
            # A leftover from a crashed/killed earlier attempt at this step
            # (e.g. after a rollback): it is garbage — clear it so the fresh
            # save does not collide with StepAlreadyExists.
            self._mngr.delete(step)
        args = ocp.args.Composite(
            format=ocp.args.JsonSave(FORMAT),
            **{name: ocp.args.StandardSave(item) for name, item in state.items()},
        )
        def _queue_write() -> bool:
            if faults.should_fire("ckpt_io_error"):
                raise IOError(
                    "injected transient checkpoint-write failure "
                    "(faults: ckpt_io_error)"
                )
            if faults.should_fire("ckpt_enospc"):
                # Disk exhaustion mid-write: partial bytes land in the step
                # directory (no manifest — the atomic commit never ran),
                # then the write dies with ENOSPC. The retry below must
                # first sweep the partial so a recovered attempt starts
                # from a clean step dir.
                if self._local:
                    d = os.path.join(self._dir, str(step))
                    os.makedirs(d, exist_ok=True)
                    with open(os.path.join(d, "partial_item.bin"), "wb") as fh:
                        fh.write(b"\x00" * 1024)
                raise OSError(
                    errno.ENOSPC,
                    "injected ENOSPC mid checkpoint write (faults: ckpt_enospc)",
                )
            self._clear_partial(step)
            return self._mngr.save(step, args=args, force=True)

        try:
            # Shared retry discipline (robustness/backoff.py) — the same
            # schedule the serving front door applies to BackpressureError.
            # The span holds only the SYNCHRONOUS queue (+ retries); the
            # TensorStore write itself is async and lands under the
            # ckpt.finalize span at the next barrier.
            with flight_recorder().tracer.span(
                "ckpt.save_queue", "ckpt", "train"
            ):
                queued = retry_with_backoff(
                    _queue_write,
                    retries=self.write_retries,
                    base_s=self.retry_backoff_sec,
                    retry_on=(OSError,),  # includes IOError; TensorStore failures
                )
        except OSError as e:
            # Budget exhausted: sweep any partial bytes a failed attempt
            # left (ENOSPC), so the step never shows up in all_steps() —
            # an un-manifested partial must not shadow the last verified
            # checkpoint nor trip a later save's StepAlreadyExists.
            self._clear_partial(step)
            raise CheckpointWriteError(
                f"checkpoint save at step {step} under {self._dir} failed "
                f"{self.write_retries} attempt(s); last error: {e}",
                step=step,
                attempts=self.write_retries,
                directory=str(self._dir),
            ) from e
        if faults.should_fire("kill_mid_save", step=step):
            # Model SIGKILL between the TensorStore write and the manifest
            # commit: bytes on disk, one item truncated, no manifest —
            # `latest_verified_step` must skip this step on resume.
            self._mngr.wait_until_finished()
            self._corrupt_one_item(step)
            raise SimulatedPreemption(f"simulated kill mid-save at step {step}")
        self._pending = step
        return bool(queued)

    def _clear_partial(self, step: int) -> None:
        """Remove an un-manifested partial step directory (the ENOSPC
        leftovers). A dir WITH a manifest is a real checkpoint — never
        touched here; verified-only GC owns its lifecycle."""
        if not self._local:
            return
        d = self._step_dir(step)
        if d is not None and not os.path.exists(os.path.join(d, MANIFEST_NAME)):
            shutil.rmtree(d, ignore_errors=True)

    def _corrupt_one_item(self, step: int) -> None:
        d = self._step_dir(step)
        if d is None:
            return
        # Truncate the largest non-manifest file (a tensor shard, in
        # practice) to half — realistic partial-write damage.
        candidates = []
        for root, _, names in os.walk(d):
            for name in names:
                if name == MANIFEST_NAME:
                    continue
                p = os.path.join(root, name)
                candidates.append((os.path.getsize(p), p))
        if not candidates:
            return
        size, path = max(candidates)
        with open(path, "rb+") as fh:
            fh.truncate(max(1, size // 2))

    def _finalize_pending(self) -> None:
        """Barrier on the in-flight async save, then commit its manifest,
        verify it, and (only on success) garbage-collect older steps."""
        step, self._pending = self._pending, None
        if step is None:
            return
        tr = flight_recorder().tracer
        with tr.span("ckpt.finalize", "ckpt", "train"):
            self._mngr.wait_until_finished()
            self._mngr.check_for_errors()
            if not self._local:
                return
            d = self._step_dir(step)
            if d is None:
                return
            # A local step directory under several processes is a SHARED
            # filesystem: one writer commits the manifest (two would race on
            # the same temp file — one os.replace wins, the other raises),
            # and everyone waits for the commit before verifying against it.
            if jax.process_index() == 0:
                write_manifest(d, step)
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                multihost_utils.sync_global_devices(f"midgpt_ckpt_manifest_{step}")
            if faults.should_fire("truncate_ckpt_item", step=step):
                # Corruption AFTER the manifest committed (bit rot / bad
                # copy): the recorded hashes no longer match the bytes.
                self._corrupt_one_item(step)
            with tr.span("ckpt.verify", "ckpt", "train"):
                problems = self.verify(step)
            if problems:
                tr.instant(
                    "ckpt.verify_failed", "ckpt", "train",
                    args={"step": step, "n_problems": len(problems)},
                )
                if jax.process_index() == 0:
                    print(
                        f"WARNING: checkpoint step {step} failed post-save "
                        "verification and will not be resumed from:\n  "
                        + "\n  ".join(problems)
                    )
                return  # keep older verified steps; no GC off an unverified save
            tr.instant("ckpt.verified", "ckpt", "train", args={"step": step})
            self._gc()

    def _gc(self) -> None:
        """Delete steps older than the `max_to_keep`-newest verified steps.

        Runs only after a fresh save verified, so the previous checkpoint
        outlives the new one's verification — a crash at any point leaves at
        least one verified step on disk."""
        verified = self.verified_steps()
        if len(verified) <= self.max_to_keep:
            return
        cutoff = verified[-self.max_to_keep]
        for s in self.all_steps():
            if s < cutoff:
                self._mngr.delete(s)

    # -- restore --------------------------------------------------------

    def restore(self, step: int, like: tp.Dict[str, tp.Any]) -> tp.Dict[str, tp.Any]:
        """Restore named items into the structure/shardings of `like` (live or
        abstract trees). Restoring a SUBSET of the saved items is supported —
        the sampler restores only {"params": ...} without touching the
        optimizer state."""
        self._finalize_pending()
        available = self.all_steps()
        if step not in available:
            raise ValueError(
                f"no checkpoint for step {step} under {self._dir}; available "
                f"steps: {available or 'none'} (verified: "
                f"{self.verified_steps() or 'none'})"
            )
        if self._has_manifest(step):
            problems = self.verify(step)
            if problems:
                raise CheckpointCorruptError(
                    f"checkpoint step {step} under {self._dir} fails integrity "
                    "verification — refusing to restore corrupt state:\n  "
                    + "\n  ".join(problems)
                    + f"\nVerified steps available: {self.verified_steps() or 'none'}",
                    step=step,
                    problems=problems,
                )
        # Validate the format marker FIRST, on its own, so a marker problem
        # (pre-v2 checkpoint, foreign layout) is diagnosed as such and a
        # genuine state-restore failure (e.g. shape mismatch) isn't.
        try:
            fmt = self._mngr.restore(
                step, args=ocp.args.Composite(format=ocp.args.JsonRestore())
            )["format"]
        except (FileNotFoundError, KeyError, ValueError) as e:
            raise ValueError(
                f"checkpoint step {step} has no readable 'format' marker — it "
                f"predates checkpoint format v{FORMAT['version']} (or is not "
                "this framework's layout) and would restore silently wrong "
                f"(see training/checkpoint.py FORMAT). Available steps: "
                f"{available}. Underlying error: {e}"
            ) from e
        if fmt != FORMAT:
            hint = (
                " If this is a v2 checkpoint (flat head-major wqkv), convert "
                "it with tools/migrate_ckpt_v2_v3.py."
                if isinstance(fmt, dict) and fmt.get("version") == 2
                else ""
            )
            raise ValueError(
                f"checkpoint format mismatch at step {step}: saved marker "
                f"{fmt}, this build reads {FORMAT} — refusing a silently-"
                f"wrong restore. Available steps under {self._dir}: "
                f"{available}.{hint}"
            )
        args = ocp.args.Composite(
            **{
                name: ocp.args.StandardRestore(_abstract_like(item))
                for name, item in like.items()
            }
        )
        restored = self._mngr.restore(step, args=args)
        return {name: restored[name] for name in like}

    # -- lifecycle ------------------------------------------------------

    def wait(self) -> None:
        self._mngr.wait_until_finished()
        self._finalize_pending()

    def close(self) -> None:
        self.wait()
        self._mngr.close()
