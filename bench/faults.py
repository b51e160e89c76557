"""Faults planted under the timed path, for showing that the output check
catches them.  The benchmark's own runs never plant one.

Each fault breaks the program where it produces its answer:

- ``token_altered``: the first token of every decode chunk is altered
  where the chunk makes it;
- ``state_unchanged``: each decode step hands back the cache it was
  given, so the next step reads no key or value of the tokens decoded
  before it;
- ``half_the_slots_left_out``: the upper half of the decode slots is
  left out; their rows hand back the token they were given.

:func:`plant` patches the program in this process and returns a function
that undoes it.  Plant a fault before the engine is built: executables
already compiled keep the sound program.
"""

from __future__ import annotations

FAULTS = ("token_altered", "state_unchanged", "half_the_slots_left_out")


def _broken_chunk(build, fault):
    def broken(model, b_kv):
        fn = build(model, b_kv)

        def wrapped(weights, kc, vc, ks, vs, tok, *args):
            out, *rest = fn(weights, kc, vc, ks, vs, tok, *args)
            if fault == "token_altered":
                out = out.at[:, 0].set((out[:, 0] + 1) % model.cfg.vocab_size)
            else:
                half = out.shape[0] // 2
                out = out.at[half:].set(tok[half:, None])
            return (out, *rest)
        return wrapped
    return broken


def _stateless_step(step):
    def broken(self, params, qcache, batch, *, b_kv):
        logits, new = step(self, params, qcache, batch, b_kv=b_kv)
        return logits, {**qcache, "len": new["len"]}
    return broken


def plant(fault: str, model_class):
    """Break the program with ``fault``; returns the undo.
    ``state_unchanged`` breaks the ``decode_step_q`` of ``model_class``,
    the class of the model the harness builds for the cell
    (``type(model)`` of ``run.build_model``)."""
    from repro.runtime import decode_engine
    if fault == "state_unchanged":
        owner, name = model_class, "decode_step_q"
        new = _stateless_step(model_class.decode_step_q)
    elif fault in FAULTS:
        owner, name = decode_engine, "_build_fused_decode"
        new = _broken_chunk(decode_engine._build_fused_decode, fault)
    else:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    old = getattr(owner, name)
    setattr(owner, name, new)
    return lambda: setattr(owner, name, old)
