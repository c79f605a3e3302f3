"""model layer: device op-milliseconds a step a chip under ``sec_attn_proj``, all
phases: attention OUTSIDE its kernels (norm, q/k/v or the low-rank chain,
rotary, ``attn @ wo``, the residual add)."""
from benchmarks import step_sections


def read(run):
    return step_sections.metric(run, ("attn_proj",))
