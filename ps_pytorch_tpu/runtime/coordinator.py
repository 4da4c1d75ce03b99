"""Coordinator — the control plane.

The semantic home of the reference's small-message wire protocol (SURVEY
§2.3): step announcement (tag 10, ``sync_replicas_master_nn.py:210-216``),
straggler kill (tag 77, ``resnet_split.py:511-523``), and the backup-worker
K-of-N cutoff (``--num-aggregate``, ``sync_replicas_master_nn.py:116,179``).

On TPU the data plane needs none of this — gradients are psum'd in-graph —
so what remains of the "master" is exactly this object: step control,
per-step participation policy, deadline enforcement, and checkpoint
authority. It runs on every host against a shared key-value store:
in-process dict on one host, the JAX coordination-service KV across hosts
(the jax.distributed client), replacing MPI point-to-point control messages
with DCN KV ops.

Policies (all host-side; the device step stays fixed-shape and just
consumes the mask vector):

- sync: everyone participates every step.
- kofn: only the K replicas with the fastest last-observed step time
  contribute (the reference master aggregates the first ``num_aggregate``
  gradient arrivals per layer and discards the rest, ``:179``).
- deadline: replicas whose last step exceeded ``kill_threshold`` seconds are
  masked out — the deadline-based re-expression of the tag-77 kill protocol
  (the reference worker aborts its backward mid-flight; here its
  contribution is simply excluded while the SPMD step completes).
"""

import json
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ps_pytorch_tpu.telemetry.trace import span as _span


class LeaderLost(RuntimeError):
    """The leader's lease went stale while a follower waited on it.

    Raised from the follower's mask wait so a dead leader surfaces as a
    clear, immediate signal instead of a 300 s TimeoutError with no cause
    attached. With an election wired (elastic/election.py) this is caught
    INSIDE participation_mask and answered by a campaign — it only
    escapes when elections are off or the campaign itself fails
    (partition), where auto-resume is the escalation."""


class KVStore:
    """Minimal KV interface. In-process default; DistributedKV over the JAX
    coordination service for multi-host (replaces MPI tags over DCN)."""

    def __init__(self):
        self._d: Dict[str, str] = {}
        self._lock = threading.Lock()

    def set(self, key: str, value: str) -> None:
        with self._lock:
            self._d[key] = value

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        with self._lock:
            return self._d.get(key, default)

    def delete(self, key: str) -> None:
        with self._lock:
            self._d.pop(key, None)

    def keys(self, prefix: str = "") -> List[str]:
        """Keys under ``prefix`` (in-process store only — the distributed
        backend has no scan; tests and in-process drills use this)."""
        with self._lock:
            return sorted(k for k in self._d if k.startswith(prefix))


class DistributedKV(KVStore):
    """KV over the JAX coordination service (available after
    ``jax.distributed.initialize``); keys are visible to every host."""

    def __init__(self):
        super().__init__()
        import jax
        if not jax.distributed.is_initialized():
            raise RuntimeError("jax.distributed not initialized")
        # jax 0.9 has no public handle on the coordination-service client.
        from jax._src import distributed
        self._client = distributed.global_state.client

    def set(self, key: str, value: str) -> None:
        # Coordination-service keys are write-once by default; control-plane
        # keys (step announce, durations) are deliberately last-writer-wins.
        self._client.key_value_set(key, value, allow_overwrite=True)

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        try:
            return self._client.key_value_try_get(key)
        except Exception as e:
            # Only "key not published yet" maps to the default; a dead or
            # unreachable coordination service must surface, not be polled.
            if "NOT_FOUND" in str(e):
                return default
            raise

    def delete(self, key: str) -> None:
        try:
            self._client.key_value_delete(key)
        except Exception as e:
            if "NOT_FOUND" not in str(e):
                raise


class FileKV(KVStore):
    """KV over a shared directory — the serving fleet's control plane.

    The coordination-service KV needs every process present at
    ``jax.distributed.initialize`` and cannot survive members dying and
    rejoining, which is exactly what a serving fleet does (replica
    SIGKILL, rolling restart). A directory on shared storage has the
    right lifecycle instead: each key is one file, writes go through a
    tmp file + ``os.replace`` so readers never see a torn value, and a
    restarted replica just overwrites its own record. Values are tiny
    JSON control records (replica registrations, heartbeats), so a
    listdir-based ``keys()`` scan stays O(fleet size)."""

    def __init__(self, root: str):
        super().__init__()
        import os
        self._root = root
        os.makedirs(root, exist_ok=True)

    @staticmethod
    def _fname(key: str) -> str:
        from urllib.parse import quote
        return quote(key, safe="")

    def set(self, key: str, value: str) -> None:
        import os
        import tempfile
        path = os.path.join(self._root, self._fname(key))
        fd, tmp = tempfile.mkstemp(dir=self._root, prefix=".tmp-")
        try:
            with os.fdopen(fd, "w") as f:
                f.write(value)
                # Durability, not just atomicity: rename alone survives
                # process death but a host power cut can commit the
                # rename while the DATA is still in the page cache —
                # readers would then see an empty/torn "committed" key.
                # fsync the bytes before the rename, and the directory
                # after it so the rename itself is on disk too.
                f.flush()
                os.fsync(fd)
            os.replace(tmp, path)
            dfd = os.open(self._root,
                          os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        import os
        path = os.path.join(self._root, self._fname(key))
        try:
            with open(path, "r") as f:
                return f.read()
        except (FileNotFoundError, OSError):
            return default

    def delete(self, key: str) -> None:
        import os
        try:
            os.unlink(os.path.join(self._root, self._fname(key)))
        except OSError:
            pass

    def keys(self, prefix: str = "") -> List[str]:
        import os
        from urllib.parse import unquote
        try:
            names = os.listdir(self._root)
        except OSError:
            return []
        out = []
        for n in names:
            if n.startswith(".tmp-"):
                continue
            k = unquote(n)
            if k.startswith(prefix):
                out.append(k)
        return sorted(out)


class Coordinator:
    def __init__(self, n_replicas: int, mode: str = "sync",
                 num_aggregate: int = 0, kill_threshold: float = 0.0,
                 kv: Optional[KVStore] = None, run_id: str = "run",
                 leader: bool = True, mask_gc_window: int = 50,
                 liveness=None, lease_interval_s: float = 0.0,
                 lease_timeout_s: float = 0.0, clock=None,
                 election=None, membership=None, liveness_factory=None):
        if mode not in ("sync", "kofn", "async"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "kofn" and not (0 < num_aggregate <= n_replicas):
            raise ValueError(
                f"kofn requires 0 < num_aggregate <= {n_replicas}, got {num_aggregate}")
        self.n = n_replicas
        self.mode = mode
        self.k = num_aggregate
        self.kill_threshold = kill_threshold
        self.kv = kv or KVStore()
        self.run_id = run_id
        self.leader = leader
        self.mask_gc_window = max(int(mask_gc_window), 2)
        # Optional resilience/heartbeat.LivenessMonitor (leader-side): folds
        # missed-heartbeat liveness into the mask — a CRASHED host is a
        # different failure than a SLOW one (kofn/deadline act on durations
        # a dead host stops reporting).
        self.liveness = liveness
        # Leader lease (--leader-lease-s): the leader refreshes one KV key
        # alongside its other control-plane writes; followers treat a stale
        # lease as leader DEATH and raise LeaderLost from the mask wait
        # instead of stalling to the run deadline. 0 = lease off. Both ends
        # share a clock domain — wall time by default, one ManualClock in
        # tests (same contract as resilience/heartbeat.py).
        self.lease_interval_s = float(lease_interval_s)
        self.lease_timeout_s = float(lease_timeout_s) or \
            3.0 * self.lease_interval_s
        self.clock = clock or time.time
        self._lease_last = float("-inf")
        # Elastic control plane (elastic/): with an election wired,
        # LeaderLost stops being fatal — the mask wait campaigns instead,
        # and this Coordinator can PROMOTE itself to leader (or demote on
        # Deposed fencing) mid-run. membership is the leader-side epoch'd
        # registry folded into the mask at step boundaries;
        # liveness_factory builds a LivenessMonitor lazily when a follower
        # is promoted (followers are constructed without one).
        self.election = election
        self.membership = membership
        self._liveness_factory = liveness_factory
        self.events: list = []
        self.stats: Dict[str, int] = {"mask_changes": 0}
        # Follower mask-wait backoff (resilience/retry.py): starts at the
        # old 2 ms poll, backs off exponentially to 100 ms, jittered so N
        # followers don't hammer the service in lockstep. Seeded by replica
        # count for determinism; each Coordinator keeps its own rng stream.
        from ps_pytorch_tpu.resilience.retry import RetryPolicy
        self._mask_backoff = RetryPolicy(base_s=0.002, max_s=0.1,
                                         jitter=0.5, seed=n_replicas)
        self._mask_rng = self._mask_backoff.delays()
        self._last_printed_mask: Optional[str] = None
        # last observed per-replica step duration (telemetry; seconds)
        self._last_duration = np.zeros(n_replicas, np.float64)
        self._killed = np.zeros(n_replicas, bool)

    # ---- step control (tag 10 equivalent) ----
    def announce_step(self, step: int) -> None:
        self.kv.set(f"{self.run_id}/step", str(step))

    def current_step(self) -> int:
        return int(self.kv.get(f"{self.run_id}/step", "0"))

    def wait_for_step(self, after: int, timeout_s: float = 300.0,
                      poll_s: float = 0.01) -> int:
        """Worker-side: spin until the announced step advances past ``after``
        (the reference worker's step-sync spin, ``distributed_worker.py:129-143``)."""
        deadline = time.monotonic() + timeout_s
        while True:
            cur = self.current_step()
            if cur > after:
                return cur
            if time.monotonic() > deadline:
                raise TimeoutError(f"step did not advance past {after}")
            time.sleep(poll_s)

    # ---- telemetry ----
    def report_duration(self, replica: int, step: int, seconds: float) -> None:
        """Record a replica's last true step duration.

        Granularity contract: durations are HOST wall times — a host reports
        the same value for every replica it owns, because replicas within an
        SPMD host step in lockstep (there is no meaningful per-device step
        time to observe; the program is one dispatch). Stragglers are
        host-level events (preemption, network, thermal), which is also what
        the reference's per-worker timers measured (distributed_worker.py:
        169-173 — one process per worker = one clock per "host").
        Consequence for kofn: see _decide_mask."""
        self._last_duration[replica] = seconds
        self.kv.set(f"{self.run_id}/dur/{replica}", json.dumps([step, seconds]))

    def pull_durations(self) -> np.ndarray:
        for r in range(self.n):
            v = self.kv.get(f"{self.run_id}/dur/{r}")
            if v is not None:
                _, s = json.loads(v)
                self._last_duration[r] = s
        return self._last_duration

    # ---- participation policy (num_aggregate / tag 77 equivalents) ----
    def participation_mask(self, step: int, timeout_s: float = 300.0) -> np.ndarray:
        """float32[n] mask for step ``step``'s in-graph masked psum.

        Every participant in an SPMD step must consume the SAME mask or
        parameters diverge, so exactly one coordinator (``leader=True``,
        process 0) decides it and publishes it on the KV; followers block on
        the published value — the announce/consume discipline of the
        reference's tag-10 step broadcast, applied to the mask.
        """
        key = f"{self.run_id}/mask/{step}"
        # Ambient span (telemetry/trace.py): on the follower this measures
        # the mask-wait — the control-plane stall a straggling leader
        # inflicts on everyone else — and on the leader the decide+publish.
        with _span("coordinator_mask", step=step):
            if self.election is None:
                if not self.leader:
                    return self._await_mask(key, step, timeout_s)
                return self._decide_and_publish_mask(key, step)
            # Elastic: leadership can change hands inside one mask wait.
            # A deposed leader demotes and falls through to the follower
            # wait; a follower whose wait raises LeaderLost campaigns and
            # either promotes (then decides this very mask) or follows the
            # new winner's lease.
            from ps_pytorch_tpu.elastic.election import Deposed
            while True:
                if self.leader:
                    try:
                        return self._decide_and_publish_mask(key, step)
                    except Deposed:
                        self._demote(step)
                        continue
                try:
                    return self._await_mask(key, step, timeout_s)
                except LeaderLost:
                    self._failover(step)

    # ---- elastic failover (election wired; elastic/election.py) ----
    def _failover(self, step: int) -> None:
        """A follower's mask wait saw a stale lease: campaign. Winning
        promotes this Coordinator to mask authority for the new epoch;
        losing means a peer claimed a fresh lease and the wait resumes
        against it. ElectionFailed (no leader after bounded rounds)
        propagates — that is a partition, and auto-resume's restart path
        is the right escalation."""
        self.stats["elections"] = self.stats.get("elections", 0) + 1
        won = self.election.campaign()
        self.stats["leader_epoch"] = self.election.epoch
        if won:
            self.leader = True
            self._lease_last = float("-inf")
            self._last_printed_mask = None  # log the takeover mask
            if self.liveness is None and self._liveness_factory is not None:
                self.liveness = self._liveness_factory()
            print(f"ELECTED leader epoch {self.election.epoch} "
                  f"at step {step}")
            self.events.append({"event": "elected",
                                "epoch": self.election.epoch,
                                "step": int(step),
                                "t": round(self.clock(), 3)})
        else:
            print(f"FOLLOW leader {self.election.owner} "
                  f"epoch {self.election.epoch} at step {step}")
            self.events.append({"event": "follow",
                                "epoch": self.election.epoch,
                                "owner": self.election.owner,
                                "step": int(step),
                                "t": round(self.clock(), 3)})

    def _demote(self, step: int) -> None:
        """Epoch fencing fired mid-publish: a higher epoch owns the lease,
        so this process's mask authority is gone. Its in-flight mask write
        may have landed, but the new leader re-publishes the same key —
        last-writer-wins converges on the new epoch's decision."""
        self.leader = False
        self.stats["deposed"] = self.stats.get("deposed", 0) + 1
        self.stats["leader_epoch"] = self.election.epoch
        print(f"DEPOSED at step {step}: following leader "
              f"{self.election.owner} epoch {self.election.epoch}")
        self.events.append({"event": "deposed",
                            "epoch": self.election.epoch,
                            "owner": self.election.owner,
                            "step": int(step),
                            "t": round(self.clock(), 3)})

    def _await_mask(self, key: str, step: int, timeout_s: float) -> np.ndarray:
        """Follower-side mask wait: jittered exponential backoff (the
        resilience/retry.py policy, de-synchronized across followers by the
        replica-count seed) instead of the old fixed 2 ms hammer, and
        TRANSIENT KV errors are absorbed as "not published yet" rather than
        killing the follower mid-wait. The deadline is still authoritative
        (a leader that never publishes remains a TimeoutError) — but with a
        leader lease configured, a STALE lease short-circuits the wait into
        LeaderLost: "the leader is dead" is a different, actionable failure
        vs "the leader is slow"."""
        deadline = time.monotonic() + timeout_s
        attempt = 0
        while True:
            try:
                v = self.kv.get(key)
            except Exception as e:
                from ps_pytorch_tpu.resilience.retry import is_retryable
                if not is_retryable(e):
                    raise
                self.stats["mask_wait_errors"] = \
                    self.stats.get("mask_wait_errors", 0) + 1
                v = None
            if v is not None:
                return np.asarray(json.loads(v), np.float32)
            self._check_lease(step)
            now = time.monotonic()
            if now > deadline:
                raise TimeoutError(f"no mask published for step {step}")
            delay = self._mask_backoff.delay(attempt, self._mask_rng)
            time.sleep(min(delay, max(deadline - now, 0.0)))
            # Cap the exponent: the wait is open-ended (attempt count is not
            # bounded by a max_attempts), so let the delay saturate at max_s
            # instead of overflowing multiplier**attempt.
            attempt = min(attempt + 1, 30)

    # ---- leader lease (death detection; resilience/heartbeat.py idiom) ----
    def _refresh_lease(self, step: int) -> None:
        """Leader-side: refresh the lease key, throttled to the interval
        (one tiny KV write per interval, rides the mask publish cadence)."""
        if self.election is not None:
            # Epoch-fenced lease (elastic/election.py): the refresh itself
            # verifies ownership unthrottled and raises Deposed when a
            # higher epoch claimed — the caller (participation_mask)
            # demotes. The legacy [step, ts] lease key is not written.
            self.election.refresh(step)
            return
        if self.lease_interval_s <= 0 or not self.leader:
            return
        now = self.clock()
        if now - self._lease_last < self.lease_interval_s:
            return
        self._lease_last = now
        self.kv.set(f"{self.run_id}/lease", json.dumps([step, now]))

    def _check_lease(self, step: int) -> None:
        """Follower-side: raise LeaderLost when the lease exists but went
        stale. A never-published lease is bootstrap grace (the leader may
        not have reached its first publish); transient KV errors are
        absorbed exactly like the mask read itself."""
        if self.leader:
            return
        if self.election is not None:
            try:
                status = self.election.check()
            except Exception as e:
                from ps_pytorch_tpu.resilience.retry import is_retryable
                if not is_retryable(e):
                    raise
                self.stats["mask_wait_errors"] = \
                    self.stats.get("mask_wait_errors", 0) + 1
                return
            if status == "stale":
                self.stats["leader_lost"] = \
                    self.stats.get("leader_lost", 0) + 1
                raise LeaderLost(
                    f"leader epoch {self.election.epoch} lease stale "
                    f"(> {self.election.timeout_s}s) waiting for step "
                    f"{step}'s mask")
            return
        if self.lease_interval_s <= 0:
            return
        try:
            v = self.kv.get(f"{self.run_id}/lease")
        except Exception as e:
            from ps_pytorch_tpu.resilience.retry import is_retryable
            if not is_retryable(e):
                raise
            self.stats["mask_wait_errors"] = \
                self.stats.get("mask_wait_errors", 0) + 1
            return
        if v is None:
            return
        lease_step, ts = json.loads(v)
        age = self.clock() - ts
        if age > self.lease_timeout_s:
            self.stats["leader_lost"] = self.stats.get("leader_lost", 0) + 1
            raise LeaderLost(
                f"leader lease stale by {age:.2f}s (> {self.lease_timeout_s}"
                f"s) waiting for step {step}'s mask; last refresh at its "
                f"step {lease_step}")

    def _decide_and_publish_mask(self, key: str, step: int) -> np.ndarray:
        self._refresh_lease(step)
        if self.membership is not None:
            # Fold announcements/liveness into the epoch'd view at the
            # step boundary (publishes {run}/member/view on change).
            self.membership.update(step)
        mask = self._decide_mask()
        # Observability: one stable line whenever the decision changes (the
        # reference's only straggler evidence was per-worker timing logs).
        desc = json.dumps(mask.astype(int).tolist())
        if desc != self._last_printed_mask:
            print(f"MASK step {step} {desc}")
            if self._last_printed_mask is not None:
                self.stats["mask_changes"] += 1
            self._last_printed_mask = desc
        self.kv.set(key, json.dumps(mask.tolist()))
        # GC with a WIDE window, not step-2: JAX dispatch is async and
        # followers only synchronize when metrics materialize (log_every), so
        # a follower can lag many host-loop iterations behind the leader —
        # deleting a mask it has not yet read would strand it in a 300 s
        # TimeoutError (round-1 advisor, medium). Masks are ~n_replicas
        # floats, so retaining `mask_gc_window` of them is still O(1).
        if step >= self.mask_gc_window:
            self.kv.delete(f"{self.run_id}/mask/{step - self.mask_gc_window}")
        return mask

    def _decide_mask(self) -> np.ndarray:
        # Kills are a KV protocol (tag-77 equivalent): pull every replica's
        # kill key so a kill issued on ANY process reaches the leader's
        # mask, not just kills issued through this object (the local
        # ``_killed`` array alone missed cross-process kills).
        self._refresh_kills()
        mask = (~self._killed).astype(np.float32)
        if self.membership is not None:
            # Elastic membership (elastic/membership.py): admissions and
            # evictions fold in at this step boundary — the registry's own
            # all-ones degenerate view (nobody announced yet) keeps the
            # static world intact, and the never-wedge fallbacks below
            # apply to membership exactly as to liveness.
            mview = np.asarray(
                self.membership.mask(), np.float32)[:self.n]
            if mview.any():
                mask *= mview
        if self.liveness is not None:
            # Missed-heartbeat eviction (graceful degradation, distinct
            # from kofn slowness); a fully-dead view falls through to the
            # never-wedge fallback below rather than masking everyone.
            alive = np.asarray(self.liveness.alive_mask(), bool)
            if alive.any():
                mask *= alive.astype(np.float32)
        if self.mode == "sync":
            if mask.sum() == 0:
                mask = (~self._killed).astype(np.float32)
                if mask.sum() == 0:
                    mask = np.ones(self.n, np.float32)
            return mask
        dur = self.pull_durations()
        if self.kill_threshold > 0:
            mask *= (dur <= self.kill_threshold).astype(np.float32)
        if self.mode == "kofn" and self.k < self.n:
            # Fastest-K by last observed duration ~ "first K gradient
            # arrivals" (sync_replicas_master_nn.py:179). Durations are
            # host-granular (see report_duration), so selection is sharp
            # BETWEEN hosts and degenerates to the stable-sort tiebreak
            # (lower replica index first) WITHIN a host — i.e. K-of-N drops
            # slow HOSTS' replicas first, then lowest-indexed replicas of
            # the boundary host. That is the right cut on real hardware:
            # within-host replicas finish together by construction.
            alive = np.nonzero(mask > 0)[0]
            if len(alive) > self.k:
                keep = alive[np.argsort(dur[alive], kind="stable")[:self.k]]
                mask = np.zeros(self.n, np.float32)
                mask[keep] = 1.0
        if mask.sum() == 0:
            # Never let the run wedge: fall back to everyone (the reference
            # master always waits for all arrivals eventually, :184-186).
            mask = (~self._killed).astype(np.float32)
            if mask.sum() == 0:
                mask = np.ones(self.n, np.float32)
        return mask

    # ---- kill protocol (tag 77 equivalent) ----
    def kill(self, replica: int) -> None:
        self._killed[replica] = True
        self.kv.set(f"{self.run_id}/kill/{replica}", "1")

    def is_killed(self, replica: int) -> bool:
        return self.kv.get(f"{self.run_id}/kill/{replica}") == "1"

    def _refresh_kills(self) -> None:
        """Fold KV kill keys into the local kill set. Kills are permanent
        (matching the reference's tag-77 semantics: a killed worker never
        rejoins), so only 0->1 transitions are read."""
        for r in range(self.n):
            if not self._killed[r] and \
                    self.kv.get(f"{self.run_id}/kill/{r}") == "1":
                self._killed[r] = True
