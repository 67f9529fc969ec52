"""Faults planted under the harness's timed path, each of which a sound
correctness check has to catch (``correct`` false)."""
from __future__ import annotations


def _token_altered(setattr):
    """Every served token is the next id after the one the logits chose."""
    from repro.launch import serve

    greedy = serve._greedy

    def altered(logits, vocab):
        return (greedy(logits, vocab) + 1) % vocab

    setattr(serve, "_greedy", altered)


def _state_unchanged(setattr):
    """The decode step returns the KV cache it was given."""
    from repro.models import model_zoo as Z

    step = Z.decode_step

    def unchanged(cfg, params, cache, tokens, pos, **kw):
        logits, _ = step(cfg, params, cache, tokens, pos, **kw)
        return logits, cache

    setattr(Z, "decode_step", unchanged)


def _half_batch(setattr):
    """Only the first half of each round's requests is served; the second
    half is handed the first half's tokens."""
    import jax.numpy as jnp

    from repro.launch import serve

    generate = serve.generate

    def half(cfg, prefill, step, params, cache, prompts, gen):
        tokens, logits = generate(cfg, prefill, step, params, cache,
                                  prompts, gen)
        h = tokens.shape[0] // 2
        return jnp.concatenate([tokens[:h], tokens[:h]], axis=0), logits

    setattr(serve, "generate", half)


def _exchange_left_out(setattr):
    """The EP exchange between chips is left out: every all-to-all of the
    dispatch and the combine keeps only the block a chip addresses to
    itself, so each chip applies its own experts to its own tokens'
    choices alone, and the choices of other chips' experts add nothing."""
    import jax.numpy as jnp
    from jax import lax

    from repro.core import ep

    def own_block(x, axis_name, split_axis, concat_axis, tiled=False):
        shape = [1] * x.ndim
        shape[split_axis] = x.shape[split_axis]
        mine = jnp.arange(x.shape[split_axis]) == lax.axis_index(axis_name)
        return jnp.where(mine.reshape(shape), x, jnp.zeros_like(x))

    class NoExchange:
        all_to_all = staticmethod(own_block)

        def __getattr__(self, name):
            return getattr(lax, name)

    setattr(ep, "lax", NoExchange())


FAULTS = {"token_altered": _token_altered,
          "state_unchanged": _state_unchanged,
          "half_batch": _half_batch,
          "exchange_left_out": _exchange_left_out}


def plant(name: str, setattr=setattr) -> None:
    FAULTS[name](setattr)
