"""ps_pytorch_tpu — TPU-native data-parallel training framework.

A ground-up JAX/XLA/pjit/Pallas re-design of the capabilities of the reference
parameter-server system ``bapi/ps_pytorch`` (see SURVEY.md at the repo root):
synchronous / asynchronous data-parallel SGD for LeNet / ResNet / VGG on
MNIST / CIFAR-10 / CIFAR-100 / SVHN / Digits (real, zero-egress), with K-of-N
backup-worker straggler mitigation, gradient compression at DCN boundaries
(lossless C++ codec or on-device Pallas int8), checkpoint-and-poll
evaluation, a native C++ loader core, and pod provisioning + launch tooling.
Beyond the reference: a transformer LM entry point (``train_lm.py``) with
the full DP/TP/PP/SP/EP/ZeRO parallelism inventory — sequence-parallel ring
attention for long context, Megatron-style tensor parallelism (GSPMD),
a GPipe pipeline differentiated through its own schedule, switch-MoE
expert parallelism with cross-process all_to_all routing, ZeRO-1 sharded
updates, per-block rematerialization — plus byte-level real-corpus
training and a standalone evaluator that scores LM checkpoints.

Design (vs. the reference's master/worker MPI loop,
``sync_replicas_master_nn.py:133-197`` / ``distributed_worker.py:104-180``):
per-step gradient exchange is an in-graph ``psum`` allreduce over the ICI
device mesh inside one jitted SPMD step; the "master" degenerates to a
coordinator-only role (step control, K-of-N participation, checkpoint
authority) with no gradient round-trip.
"""

__version__ = "0.1.0"

from ps_pytorch_tpu.config import TrainConfig  # noqa: F401
