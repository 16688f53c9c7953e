"""Training traffic needs no schedule: the cell runner draws the host
array of batches, [host_batches, batch, seq + 1] token ids, from the seed
inside the worker that owns the chips (benchmark/cells/train.py). This
file states the kind's traffic keys: ``mesh_axes``, ``batch``, ``seq``,
``lr``, ``host_batches``, ``warmup_steps``, ``trace_from_step``,
``trace_steps``, ``check.loss_tolerance``. Every seed gives every step
the same shape and the same amount of work; only the ids differ.
"""


def generate(traffic, seed, vocab):
    return None
