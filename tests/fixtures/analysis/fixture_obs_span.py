"""Lint fixture: program spans (``repro.obs.span``) in the wrong places.
Never imported — the auditor parses it (pure AST).  The test pins
``tick_fn`` as a tick jit and ``hot_step`` as a hot root; exactly two
``obs-hot-path`` violations must fire at the marked lines:

* a span opened inside the tick-jit body (spans are host-side only);
* a span in the hot path fed a device-tracked value (recording it
  would materialise the array).

The span in ``hot_step`` that carries only a name and the recorder is
the sanctioned shape and must NOT fire."""

import jax.numpy as jnp

from repro import obs


def tick_fn(tokens, caches, recorder):
    with obs.span("step.inner", recorder):  # LINT-EXPECT: obs-hot-path
        logits = jnp.dot(tokens, caches)
    return logits


def hot_step(rec, tokens):
    with obs.span("step.decode_dispatch", rec):           # host only: fine
        logits = jnp.asarray(tokens)
    with obs.span("sched.decode_results", rec, (("top", logits[0]),)):  # LINT-EXPECT: obs-hot-path
        pass
    return logits
