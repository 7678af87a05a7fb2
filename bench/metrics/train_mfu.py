"""Model FLOPs utilisation of the train step: model FLOPs per token (the
architecture's ``flops_per_token``, bench/arch.py; recomputation not
counted) times the traced window's tokens per second, over the chip's
bf16 peak (bench/peaks.json)."""
from bench import arch, program


def read(run):
    rate = run.e2e.get("train_tokens_per_s")
    if not rate:
        return None
    f = arch.of(run.config).flops_per_token(program.sizes(run.config),
                                            run.traffic["seq"])
    return 100.0 * f * rate / (run.peaks["bf16_flops"] * len(run.devices))
