"""Faults planted in the decision program's outputs, where they are
produced, to show that the check fails a broken timed path.

Each takes the step's arguments and outputs, (choice, est_T, l_chosen,
d, b, free, ctx, d1, b1, f1), and returns broken outputs:

- `state_unchanged`: the dead-reckoned state after the scan is the
  state before it;
- `pending_frozen`: the pending work is never dead-reckoned (batch and
  free slots still are);
- `half_batch`: the second half of each window's valid rows is never
  decided: they all get instance 0, with the estimates of the first row;
- `answer_altered`: each window's first pick moved to the next instance.

`bench/test_faults.py` drives them on the CPU, `bench/control.py
--fault <name>` on the chip.
"""
import jax.numpy as jnp


def state_unchanged(args, out):
    d, b, free = (jnp.array(x, copy=True) for x in out[3:6])
    return out[:7] + (d, b, free)


def pending_frozen(args, out):
    return out[:7] + (jnp.array(out[3], copy=True),) + out[8:]


def half_batch(args, out):
    rv = args[1]                                  # row_valid (Rb,)
    n = jnp.sum(rv.astype(jnp.int32))
    row = jnp.arange(rv.shape[0])
    left_out = row >= (n + 1) // 2
    choice = jnp.where(left_out, 0, out[0])
    l_chosen = jnp.where(left_out, out[2][0], out[2])
    est = jnp.where(left_out, out[1][0], out[1])
    return (choice, est, l_chosen) + out[3:]


def answer_altered(args, out):
    n_real = jnp.sum(args[8].astype(jnp.int32))   # live instances
    choice = out[0].at[0].set((out[0][0] + 1) % n_real)
    return (choice,) + out[1:]


FAULTS = {f.__name__: f for f in (state_unchanged, pending_frozen,
                                  half_batch, answer_altered)}
