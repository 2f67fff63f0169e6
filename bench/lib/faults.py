"""Faults planted underneath the timed path of the federated cells.

Each is a context manager that breaks one thing in the program while
it is active; the comparison with the reference has to come out not
correct.  Used by ``bench/tests/test_faults.py`` (CPU, small size) and
by ``bench/control.py --fault`` (the chip, the cell's own size).

- ``state_unchanged``: the fit returns the state it was given;
- ``half_batch``: the fit sees the first half of each batch only, so
  the loss and gradients are means over the rest;
- ``upload_altered``: client 0's upload bits are inverted where the
  pack kernel produces them;
- ``exchange_left_out``: the popcount psum between chips is skipped,
  each chip averages its own upload alone.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name, make):
    old = getattr(obj, name)
    setattr(obj, name, make(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


def _state_unchanged(fit):
    def broken(zspecs, state, loss_fn, batches, key, cfg, **kw):
        _, mets = fit(zspecs, state, loss_fn, batches, key, cfg, **kw)
        return state, mets
    return broken


def _half_batch(fit):
    def broken(zspecs, state, loss_fn, batches, key, cfg, **kw):
        import jax

        def half(x):  # (R, K, E, B, ...): keep the first B/2 rows
            return x[:, :, :, : x.shape[3] // 2]
        return fit(zspecs, state, loss_fn, jax.tree.map(half, batches),
                   key, cfg, **kw)
    return broken


def _upload_altered(pack):
    def broken(spec, P, steps, impl):
        lanes = pack(spec, P, steps, impl)
        return lanes.at[0].set(~lanes[0])
    return broken


def _exchange_left_out(_):
    def broken(self, lanes, n, axis_names):
        import jax.numpy as jnp

        from repro.comm.bitpack import unpack_mask
        del self, axis_names
        return unpack_mask(lanes, n, dtype=jnp.float32)
    return broken


def planted(name: str):
    """Context manager that plants fault ``name``."""
    import repro.train
    from repro.comm.protocol import PsumU32
    from repro.kernels import ops

    return {
        "state_unchanged": lambda: _patched(repro.train, "federated_fit",
                                            _state_unchanged),
        "half_batch": lambda: _patched(repro.train, "federated_fit",
                                       _half_batch),
        "upload_altered": lambda: _patched(ops, "_pack_many",
                                           _upload_altered),
        "exchange_left_out": lambda: _patched(
            PsumU32, "aggregate_collective_packed", _exchange_left_out),
    }[name]()


FAULTS = ("state_unchanged", "half_batch", "upload_altered",
          "exchange_left_out")
