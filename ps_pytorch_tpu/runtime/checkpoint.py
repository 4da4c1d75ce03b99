"""Step-named checkpoints with atomic commit and resume.

Reproduces the reference's checkpoint contract — ``train_dir/model_step_<k>``
written every ``eval_freq`` steps for a polling evaluator
(``sync_replicas_master_nn.py:264-270``, ``distributed_evaluator.py:74-88``) —
and closes its biggest gap: the reference cannot resume (training always
starts at step 1, ``sync_replicas_master_nn.py:18``); here ``load_checkpoint``
restores params, optimizer state, replica-local BN stats, and the config.

Layout: ``train_dir/model_step_<k>/`` containing ``state.msgpack`` (flax
serialization of the TrainState pytree), ``config.json``, ``meta.json``.
Atomic commit: write into ``train_dir/.tmp_<k>`` then ``os.rename`` — the
evaluator can never observe a half-written checkpoint (the reference's
torch.save to NFS has no such guarantee).

Optional codec compression (``compress=True``) applies the native
blosc-equivalent to the serialized bytes — the checkpoint/DCN leg of the
reference's ``--compress-grad`` capability (``compression.py``).

Hardening (resilience layer): every checkpoint carries a ``manifest.json``
with per-file SHA-256 digests, written inside the tmp dir BEFORE the atomic
rename — so "committed" now means "committed AND content-addressed". Loads
verify the manifest first and raise :class:`CheckpointCorruptError` on any
mismatch; ``latest_valid_step``/``load_latest_valid`` walk past torn or
bit-rotted checkpoints to the newest one that verifies, and
``prune_checkpoints`` implements keep-last-N retention. Pre-manifest
checkpoints stay loadable (existence-checked only).
"""

import hashlib
import json
import os
import re
import shutil
from typing import Any, List, Optional, Tuple

import jax
import numpy as np
from flax import serialization

from ps_pytorch_tpu.telemetry.trace import span as _span

_STEP_RE = re.compile(r"^model_step_(\d+)$")
_MANIFEST = "manifest.json"


class CheckpointCorruptError(RuntimeError):
    """A committed checkpoint failed integrity verification (missing file,
    SHA-256 mismatch, unreadable manifest). Resume paths catch this and
    fall back to the previous valid step."""


def checkpoint_path(train_dir: str, step: int) -> str:
    return os.path.join(train_dir, f"model_step_{step}")


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def save_checkpoint(train_dir: str, step: int, state: Any,
                    config_json: str = "{}", compress: bool = False,
                    codec_level: int = 3, extra_meta: Optional[dict] = None,
                    extra_state: Optional[Any] = None) -> str:
    """Atomically write train_dir/model_step_<step>. Returns the final path.

    ``extra_state``: optional auxiliary pytree (e.g. error-feedback
    residuals) committed alongside the model as ``extra_state.msgpack`` —
    same atomic rename, same manifest coverage, restored via
    :func:`load_extra_state`.
    """
    with _span("checkpoint_write", step=step):
        return _save_checkpoint(train_dir, step, state, config_json,
                                compress, codec_level, extra_meta,
                                extra_state)


def _save_checkpoint(train_dir: str, step: int, state: Any,
                     config_json: str, compress: bool,
                     codec_level: int, extra_meta: Optional[dict],
                     extra_state: Optional[Any] = None) -> str:
    os.makedirs(train_dir, exist_ok=True)
    state = jax.device_get(state)
    blob = serialization.to_bytes(state)
    meta = {"step": step, "compressed": bool(compress), **(extra_meta or {})}
    if compress:
        from ps_pytorch_tpu.compression import w_compress
        blob = w_compress(np.frombuffer(blob, np.uint8), level=codec_level)
    # Pid-suffixed tmp (a restarted writer must not collide with a stale tmp
    # from a crashed predecessor); sweep any stale tmps for this step first
    # so crash/restart cycles don't accumulate full serialized models.
    for name in os.listdir(train_dir):
        if name.startswith(f".tmp_{step}_"):
            shutil.rmtree(os.path.join(train_dir, name), ignore_errors=True)
    tmp = os.path.join(train_dir, f".tmp_{step}_{os.getpid()}")
    final = checkpoint_path(train_dir, step)
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    with open(os.path.join(tmp, "state.msgpack"), "wb") as f:
        f.write(blob)
    with open(os.path.join(tmp, "config.json"), "w") as f:
        f.write(config_json)
    if extra_state is not None:
        with open(os.path.join(tmp, "extra_state.msgpack"), "wb") as f:
            f.write(serialization.to_bytes(jax.device_get(extra_state)))
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
    # Integrity manifest, inside the tmp dir so the rename commits data and
    # digests together — a checkpoint can never be "committed but
    # unverifiable".
    manifest = {"step": step, "algo": "sha256",
                "files": {name: _sha256_file(os.path.join(tmp, name))
                          for name in sorted(os.listdir(tmp))}}
    with open(os.path.join(tmp, _MANIFEST), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):  # overwrite-last-wins, like the workers' NFS writes
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def load_checkpoint(train_dir: str, step: int, target: Any
                    ) -> Tuple[Any, dict, str]:
    """-> (state_like_target, meta, config_json). A stored tree whose
    structure does not match ``target`` raises (flax's from_state_dict)."""
    with _span("checkpoint_load", step=step):
        return _load_checkpoint(train_dir, step, target)


def verify_checkpoint(train_dir: str, step: int) -> bool:
    """True iff model_step_<step> passes integrity verification: every
    manifest entry exists with a matching SHA-256. Pre-manifest (legacy)
    checkpoints verify by file existence only."""
    path = checkpoint_path(train_dir, step)
    if not os.path.isdir(path):
        return False
    mpath = os.path.join(path, _MANIFEST)
    if not os.path.exists(mpath):
        return all(os.path.exists(os.path.join(path, n))
                   for n in ("state.msgpack", "meta.json", "config.json"))
    try:
        _check_manifest(path)
    except CheckpointCorruptError:
        return False
    return True


def _check_manifest(path: str) -> None:
    """Raise CheckpointCorruptError on any integrity violation; no-op for
    legacy manifest-less checkpoints."""
    mpath = os.path.join(path, _MANIFEST)
    if not os.path.exists(mpath):
        return
    try:
        with open(mpath) as f:
            manifest = json.load(f)
        files = manifest["files"]
    except (ValueError, KeyError, OSError) as e:
        raise CheckpointCorruptError(f"{path}: unreadable manifest: {e}")
    for name, digest in files.items():
        fpath = os.path.join(path, name)
        if not os.path.exists(fpath):
            raise CheckpointCorruptError(f"{path}: missing {name}")
        got = _sha256_file(fpath)
        if got != digest:
            raise CheckpointCorruptError(
                f"{path}: {name} sha256 mismatch "
                f"(manifest {digest[:12]}…, file {got[:12]}…)")


def _load_checkpoint(train_dir: str, step: int, target: Any
                     ) -> Tuple[Any, dict, str]:
    path = checkpoint_path(train_dir, step)
    _check_manifest(path)
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    with open(os.path.join(path, "state.msgpack"), "rb") as f:
        blob = f.read()
    if meta.get("compressed"):
        from ps_pytorch_tpu.compression import w_decompress
        blob = w_decompress(blob).tobytes()
    with open(os.path.join(path, "config.json")) as f:
        config_json = f.read()
    state = serialization.from_state_dict(
        target, serialization.msgpack_restore(blob))
    return state, meta, config_json


def load_extra_state(train_dir: str, step: int) -> Optional[Any]:
    """Restore the auxiliary pytree committed by ``save_checkpoint(...,
    extra_state=...)`` at ``step``, or None when that checkpoint carries
    none (older checkpoints, or runs without auxiliary state). Integrity
    is manifest-checked like the main payload: the extra file rode the
    same atomic rename, so a committed checkpoint either has a verified
    copy or none at all."""
    path = checkpoint_path(train_dir, step)
    fpath = os.path.join(path, "extra_state.msgpack")
    if not os.path.exists(fpath):
        return None
    _check_manifest(path)
    with open(fpath, "rb") as f:
        return serialization.msgpack_restore(f.read())


def latest_step(train_dir: str) -> Optional[int]:
    """Largest k with a committed model_step_<k>, or None."""
    steps = committed_steps(train_dir)
    return steps[-1] if steps else None


def committed_steps(train_dir: str) -> List[int]:
    """All committed steps, ascending (committed != necessarily valid)."""
    if not os.path.isdir(train_dir):
        return []
    return sorted(int(m.group(1)) for name in os.listdir(train_dir)
                  if (m := _STEP_RE.match(name)))


def latest_valid_step(train_dir: str) -> Optional[int]:
    """Largest k whose checkpoint passes integrity verification, skipping
    corrupt/incomplete ones — what resume should trust."""
    for step in reversed(committed_steps(train_dir)):
        if verify_checkpoint(train_dir, step):
            return step
    return None


def load_latest_valid(train_dir: str, target: Any
                      ) -> Optional[Tuple[Any, dict, str, int]]:
    """Restore the newest checkpoint that both verifies AND deserializes,
    walking backwards past corrupt ones -> (state, meta, config_json,
    step), or None when nothing is restorable.

    Verification catches torn/bit-rotted files; the deserialize attempt
    additionally catches legacy manifest-less corruption. A checkpoint
    that fails for a NON-corruption reason (e.g. wrong model family) fails
    on every older step too, so if no step restores the NEWEST error is
    re-raised rather than silently training from scratch."""
    steps = committed_steps(train_dir)
    first_err: Optional[BaseException] = None
    for step in reversed(steps):
        if not verify_checkpoint(train_dir, step):
            print(f"[ckpt] step {step} failed verification; "
                  f"falling back to an older checkpoint")
            continue
        try:
            state, meta, config_json = load_checkpoint(
                train_dir, step, target)
            return state, meta, config_json, step
        except CheckpointCorruptError as e:
            print(f"[ckpt] step {step} corrupt on load ({e}); falling back")
        except Exception as e:  # noqa: BLE001 — re-raised below if global
            if first_err is None:
                first_err = e
            print(f"[ckpt] step {step} unrestorable "
                  f"({type(e).__name__}: {e}); falling back")
    if first_err is not None:
        raise first_err
    return None


def prune_checkpoints(train_dir: str, keep_last: int) -> List[int]:
    """Keep-last-N retention: remove all but the newest ``keep_last``
    committed checkpoints. Returns the removed steps."""
    if keep_last <= 0:
        return []
    steps = committed_steps(train_dir)
    drop = steps[:-keep_last] if len(steps) > keep_last else []
    for step in drop:
        shutil.rmtree(checkpoint_path(train_dir, step), ignore_errors=True)
    return drop


def wait_for_step(train_dir: str, step: int, poll_s: float = 10.0,
                  timeout_s: Optional[float] = None) -> bool:
    """Block until model_step_<step> exists (the evaluator's poll loop,
    ``distributed_evaluator.py:79-88`` — 10 s poll interval parity)."""
    import time
    waited = 0.0
    while not os.path.isdir(checkpoint_path(train_dir, step)):
        if timeout_s is not None and waited >= timeout_s:
            return False
        time.sleep(poll_s)
        waited += poll_s
    return True
