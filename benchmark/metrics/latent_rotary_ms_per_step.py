"""Device ms a step spends on the latent attention's decoupled rotary
embedding: the instructions under the scope `latent_attention/rotary`, which
`paddle_tpu.models.transformer.latent_attention` opens round the slices of each
head's q_r, the rotation of q_r and of the one k_r a token, the queries'
re-assembly and the spreading of the rotated k_r over the heads.  Forward,
backward and what a `recompute_scope` makes again, each event's own time.
XLA fuses a slice or a concatenate into its neighbour where it can: what it
fused into an instruction OUTSIDE the scope is that instruction's, so this is
the time of the instructions whose name is the rotation's, no more.  Nothing
where the program has no such scope (latent attention without positions, a
parent without the rotation)."""
import re

from benchmark.metrics import kda_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/latent_attention(_\d+)?/rotary/")


def read(ctx: dict):
    return kda_ms_per_step.own_ms_under(ctx, SCOPE)
