"""Share of the training step's busy time on the first device that ran
under the scope ``adamw`` (``train/optim.py``: global norm, clipping and
the update of every leaf); the by-scope table is on the earlier line
``train_by_scope`` (device trace)."""

from benchmark import program_trace


def read(run):
    return program_trace.scope_share(
        run, ("adamw",), run.params["device_programs"]["step"],
        "train_by_scope")
