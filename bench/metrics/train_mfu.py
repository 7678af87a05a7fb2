"""Model FLOPs utilisation of the train step: model FLOPs per token
(bench/work.py, recomputation not counted) times the traced window's
tokens per second, over the chip's bf16 peak (bench/peaks.json)."""
from bench import program, work


def read(run):
    rate = run.e2e.get("train_tokens_per_s")
    if not rate:
        return None
    f = work.flops_per_token(program.sizes(run.config), run.traffic["seq"])
    return 100.0 * f * rate / (run.peaks["bf16_flops"] * len(run.devices))
