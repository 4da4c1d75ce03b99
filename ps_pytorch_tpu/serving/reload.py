"""Hot checkpoint reload for the serving engine.

Same posture as the polling evaluator (``runtime/evaluator.py``): watch a
train dir, notice when training has committed a NEWER checkpoint, and load
it — but through ``load_latest_valid`` so a torn or bit-rotted newest
checkpoint is walked past instead of served (the corruption-fallback
contract pinned in runtime/checkpoint.py). The watcher only LOADS; the
engine swaps params between decode ticks (``ServingEngine.set_params``), so
in-flight requests keep streaming across a reload.
"""

from dataclasses import dataclass
from typing import Any, Optional

from ps_pytorch_tpu.runtime import checkpoint as ckpt


@dataclass
class ReloadResult:
    """What ``poll`` hands the drive loop when a newer valid checkpoint
    landed: the params to serve and the step they came from."""
    step: int
    params: Any
    config_json: str
    meta: dict


class CheckpointWatcher:
    """Polls ``train_dir`` for newer VALID checkpoints.

    ``template`` is the TrainState template the checkpoints deserialize
    into (``runtime/lm_eval.build_lm_template``); ``to_tree`` normalizes the
    saved param layout to the plain model tree (``build_lm_oracle``'s
    second return — pp checkpoints store stage-stacked blocks);
    ``start_step`` marks the checkpoint already being served so the first
    poll doesn't re-load it."""

    def __init__(self, train_dir: str, template: Any, *, to_tree=None,
                 start_step: int = -1):
        self.train_dir = train_dir
        self.template = template
        self.to_tree = to_tree or (lambda p: p)
        self.loaded_step = int(start_step)
        self.reloads = 0
        self.skipped_corrupt = 0
        self.poll_count = 0
        # Newest step already counted into skipped_corrupt — a corrupt
        # newest checkpoint is ONE corruption event, not one per poll.
        self._skip_counted = -1
        # Meta of the newest loaded checkpoint — elastic training runs
        # stamp leader_epoch/leader_pid here, and /healthz surfaces which
        # leadership epoch produced the weights currently being served.
        self.last_meta: dict = {}

    def poll(self) -> Optional[ReloadResult]:
        """None when nothing newer is loadable; otherwise load the newest
        valid checkpoint past ``loaded_step`` (counting any corrupt newer
        steps it had to walk past) and advance."""
        self.poll_count += 1
        newest = ckpt.latest_step(self.train_dir)
        if newest is None or newest <= self.loaded_step:
            return None
        got = ckpt.load_latest_valid(self.train_dir, self.template)
        if got is None:
            # Everything newer (indeed everything) is corrupt: keep serving
            # what we have. Count the newest step once, not every poll —
            # the counter tracks corruption EVENTS, and the same corrupt
            # newest re-observed is the same event.
            if newest != self._skip_counted:
                self.skipped_corrupt += 1
                self._skip_counted = newest
            return None
        state, meta, config_json, step = got
        if step < newest and newest != self._skip_counted:
            self.skipped_corrupt += 1
            self._skip_counted = newest
        if step <= self.loaded_step:
            return None     # newest valid is what we already serve
        self.loaded_step = step
        self.reloads += 1
        self.last_meta = dict(meta)
        return ReloadResult(step=step, params=self.to_tree(state.params),
                            config_json=config_json, meta=meta)
