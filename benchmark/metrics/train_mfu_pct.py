"""Model FLOP/s utilization: operations a trained token requires (the
family's ``train_flops_per_token``, from the configuration's shape) times
this run's tokens per second per chip, over the chip's published peak."""

from benchmark import peaks
from benchmark.loading import sibling


def read(run):
    rate = sibling(__file__, "train_tokens_per_s_chip.py").read(run)
    if rate is None:
        return None
    need = run.family.train_flops_per_token(run.config)
    return 100.0 * need * rate / peaks.peak(run.device_kind)["flops_per_s"]
