"""Share of the training step's busy time on the first device that ran
under the scope ``head_loss`` (the output head and the cross-entropy of
the model's loss function, forward and backward); the whole by-scope
table goes to the earlier line ``train_by_scope`` (device trace)."""

from benchmark import program_trace


def read(run):
    return program_trace.scope_share(
        run, ("head_loss",), run.params["device_programs"]["step"],
        "train_by_scope")
