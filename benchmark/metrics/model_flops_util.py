"""Model FLOP/s utilisation: the configuration's operations per sample
(`flops_per_sample` of its model module: what forward and backward require,
nothing recomputed) times samples per second, over chips times the bf16 peak
of the device's row in the peaks table."""
LAYER = 'XLA: device'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'host_clock'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    s = ctx["stats"]
    if "samples_per_s" not in s:
        return None
    need = ctx["model"].flops_per_sample(ctx["config"], ctx["traffic"])
    return 100.0 * need * s["samples_per_s"] / (s["chips"] * ctx["peaks"]["bf16_flops_per_s"])
