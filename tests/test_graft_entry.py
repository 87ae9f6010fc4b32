"""The driver's entry points must keep working: entry() traces, and the
multi-chip dry run executes a full hierarchical DP step on 8 devices."""

import jax
import jax.numpy as jnp

import __graft_entry__ as ge


def test_entry_traces():
    fn, args = ge.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape == (8, 1000)
    assert out.dtype == jnp.float32


def test_dryrun_multichip_8():
    ge.dryrun_multichip(8)
