"""Faults planted in the timed path, for the tests and ``control.py``."""
from __future__ import annotations


def alter_tokens(core):
    """A token altered where it is produced: the decode step hands the host
    the next answer id instead of its argmax."""
    step = core._slot_step_j

    def altered(*args, **kw):
        toks, logits, cache, index = step(*args, **kw)
        return (toks + 1) % kw["answer_vocab"], logits, cache, index
    core._slot_step_j = altered


def drop_exchange():
    """The exchange between chips left out: the tensor-parallel
    all-reduces after the attention and MLP projections become identity.
    Call before the system is built (they are read at trace time)."""
    from repro.distributed import collectives
    collectives.tp_attn_all_reduce = lambda x: x
    collectives.tp_mlp_all_reduce = lambda x: x


FAULTS = {"token": alter_tokens}
