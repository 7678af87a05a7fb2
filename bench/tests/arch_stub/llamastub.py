"""An architecture that exists only in the tests, ``LlamaStubForCausalLM``:
LLaMA's reference under another name, counting the harness's calls into
each function of the contract (``bench/arch.py``). The tests put this
directory on ``bench.reference``'s path, as if the file had been added
there."""
import collections
import functools

from bench.reference import llama

CALLS = collections.Counter()


def _counted(fn):
    @functools.wraps(fn)
    def counted(*args, **kw):
        CALLS[fn.__name__] += 1
        return fn(*args, **kw)
    return counted


sizes = _counted(llama.sizes)
check = _counted(llama.check)
layout = _counted(llama.layout)
logits = _counted(llama.logits)
loss = _counted(llama.loss)
flops_per_token = _counted(llama.flops_per_token)
