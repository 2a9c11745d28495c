"""Faults planted under the timed path, to show that `correct` catches
them: `python bench/run.py ... --fault <name>`. The benchmark's own runs
never plant one. Each patches the program in this process only, and
returns the call that undoes the patch.
"""
from __future__ import annotations


def token_altered():
    """The third token of every request is replaced by the next token id
    as the scheduler emits it (the model still decodes the original)."""
    from repro.serving.scheduler import Scheduler

    emit = Scheduler._emit

    def altered(self, slot_idx, st, tok):
        if len(st.tokens) == 2:  # the request's own count so far
            tok = (tok + 1) % self.engine.cfg.vocab_size
        return emit(self, slot_idx, st, tok)

    Scheduler._emit = altered
    return lambda: setattr(Scheduler, "_emit", emit)


def insert_skipped():
    """A prefilled prompt's KV never reaches the block pool: the insert
    returns the pool unchanged."""
    from repro.serving.engine import ServeEngine

    insert = ServeEngine.paged_insert
    ServeEngine.paged_insert = lambda self, pool, fresh, bids: pool
    return lambda: setattr(ServeEngine, "paged_insert", insert)


def state_unchanged():
    """The train step returns the state it was given (its metrics are
    still computed)."""
    from repro.train import steps

    build = steps.build_train_step

    def unchanged(cfg, ocfg, **kw):
        step = build(cfg, ocfg, **kw)
        return lambda state, batch: (state, step(state, batch)[1])

    steps.build_train_step = unchanged
    return lambda: setattr(steps, "build_train_step", build)


def half_batch():
    """The train step sees only the first half of each batch, and takes
    its mean over those rows."""
    import jax

    from repro.train import steps

    build = steps.build_train_step

    def half(cfg, ocfg, **kw):
        step = build(cfg, ocfg, **kw)
        return lambda state, batch: step(
            state, jax.tree.map(lambda x: x[:x.shape[0] // 2], batch))

    steps.build_train_step = half
    return lambda: setattr(steps, "build_train_step", build)


FAULTS = {f.__name__: f for f in (token_altered, insert_skipped,
                                  state_unchanged, half_batch)}
