"""What a prefill call of the member rows alone owes a call that carries
every slot's row (`serve/programs.py::prefill_paged` takes any rows),
checked on an engine's own program by the tests of each served model."""
import jax
import jax.numpy as jnp
import numpy as np


def _leaves(cache):
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in
            jax.tree_util.tree_flatten_with_path(cache)[0]}


def member_rows_alone_leave_what_all_rows_leave(eng, cache, pages, member=1,
                                                start=12, real=3, tol=0.0):
    """ONE member row (`member`: `real` tokens from `start`, pads after
    them) through a call of all `slots` rows, the others zero tokens at
    `max_len` (what every prefill call was until PR 48), and through a
    call of two rows, the member and a pad row that names slot `slots`:
    every leaf of the cache comes out bit for bit the same (within `tol`
    where a product's rounding follows its batch), the rows of the slot
    leaves that are no member EXACTLY as they went in, and the pad row
    lands nowhere. Returns how many slot leaves it compared."""
    S, L = eng.config.slots, eng.model_config.max_len
    i32 = lambda a: jnp.asarray(a, jnp.int32)                # noqa: E731
    toks = np.zeros((S, 8), np.int32)
    toks[member] = np.arange(5, 13)
    starts = np.full((S,), L, np.int32)
    starts[member] = start
    lengths = np.zeros((S,), np.int32)
    lengths[member] = real
    at = [member, 0]                       # what a pad row's operands hold is junk
    keeps = bool(eng._slot_state)
    every = eng._prefill(
        eng.params, cache, i32(np.arange(S)), i32(toks), i32(starts), pages,
        *((i32(lengths),) if keeps else ()))
    alone = eng._prefill(
        eng.params, cache, i32([member, S]), i32(toks[at]),
        i32([start, L]), pages[jnp.asarray(at)],
        *((i32([real, 0]),) if keeps else ()))
    before, every, alone = _leaves(cache), _leaves(every), _leaves(alone)
    others = [s for s in range(S) if s != member]
    slot_leaves = 0
    for name, x in every.items():
        assert np.abs(x - alone[name]).max() <= tol, name
        if name.rsplit("'", 2)[-2] in eng._slot_state:
            slot_leaves += 1
            assert np.array_equal(alone[name][others],
                                  before[name][others]), name
            assert not np.array_equal(alone[name][member],
                                      before[name][member]), name
    assert any(not np.array_equal(x, before[name])
               for name, x in alone.items())
    return slot_leaves
