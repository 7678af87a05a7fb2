"""Work counts and the end-to-end arithmetic: FLOPs per token (of the
LLaMA architecture), bytes from shapes, a rate over whole steps, and
tails over all requests."""
import numpy as np

from bench import work
from bench.drivers import serve, train
from bench.reference import llama


def test_flops_per_token_smollm():
    s = {"d_model": 576, "n_layers": 30, "vocab_size": 49152, "n_heads": 9,
         "n_kv_heads": 3, "d_head": 64, "d_ff": 1536}
    per_layer = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    dense = 30 * per_layer + 576 * 49152
    attn = 30 * 2 * 576 * 512.5
    assert llama.flops_per_token(s, 1024) == 6 * (dense + attn)
    assert 0.9e9 < llama.flops_per_token(s, 1024) < 0.95e9


def test_shape_bytes():
    t = "%c = f32[1024,576]{1,0} custom-call(bf16[8,64]{1,0} %a, s32[] %b)"
    assert work.shape_bytes(t) == 1024 * 576 * 4 + 8 * 64 * 2 + 4


class _Steps:
    """A step that takes 10 ms of host time."""

    def __init__(self):
        import jax.numpy as jnp
        self.one = jnp.float32(1.0)
        self.params = self.opt_state = self.one

    def step(self):
        import time
        time.sleep(0.01)
        return self.one


class _Counter:
    n = 0


def test_rate_counts_whole_steps_over_the_whole_window():
    steps, elapsed, losses, comp = train.window(_Steps(), 0.25, 2, _Counter())
    assert steps == len(losses) and comp == 0
    assert elapsed >= 0.25 and abs(elapsed - steps * 0.01) < 0.03


def test_tails_are_over_all_requests_not_chunks():
    sched = [(float(i), 64, 3) for i in range(10)]
    emits = {i: [i + 0.1 * (i + 1), i + 0.1 * (i + 1) + 0.05,
                 i + 0.1 * (i + 1) + 0.06] for i in range(10)}
    rec = {"t0": 0.0, "emits": emits, "in_window": list(range(10)),
           "ticks": [(0.5, 0.5)], "late": [0.0] * 10}
    e2e, counts = serve.summarize(rec, sched, 10.0)
    ttft = [0.1 * (i + 1) for i in range(10)]
    assert abs(counts["ttft_pct_s"][90] - np.percentile(ttft, 90)) < 1e-12
    gaps = [0.05] * 10 + [0.01] * 10
    assert abs(e2e["itl_p90_s"] - np.percentile(gaps, 90)) < 1e-9
    assert abs(counts["itl_mean_s"] - 0.03) < 1e-9
    # the last request's tokens land at 10.0 s and later, past the window
    assert counts["tokens_in_window"] == 27
    assert counts["requests_in_window"] == 10


def test_schedule_gives_every_seed_the_same_window_work():
    tr = {"rate_rps": 1.5, "warmup_s": 8, "tail_s": 40, "block_s": 7.5,
          "output_min": 16, "output_max": 64,
          "prompt_lengths": [64, 128, 256, 512],
          "prompt_weights": [0.4, 0.3, 0.2, 0.1]}
    a, b = serve.schedule(1, tr, 45), serve.schedule(2**32 + 5, tr, 45)
    win = lambda s: [x for x in s if 0 <= x[0] < 45]
    assert len(win(a)) == len(win(b)) == 68
    assert win(a) != win(b)                          # another order
    edges = np.linspace(0, 68, 7).round().astype(int)
    for lo, hi in zip(edges, edges[1:]):             # the same work per block
        for i in (1, 2):                             # prompts, outputs
            pick = lambda s: sorted(x[i] for x in win(s)[lo:hi])
            assert pick(a) == pick(b)
    assert sorted(p for _, p, _ in win(a)).count(512) == 6
    assert min(x[0] for x in a) == -8 and max(x[0] for x in a) < 45 + 40
