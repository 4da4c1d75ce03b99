"""The fixture's reference is the GPT-2 reference itself, at the fixture's
sizes."""

import os

import harness

_real = harness.load_module(os.path.join(harness.HERE, "reference",
                                         "gpt2_medium.py"))
forward = _real.forward
train_flops_per_sample = _real.train_flops_per_sample
