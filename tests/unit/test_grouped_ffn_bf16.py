"""The bfloat16 half of ``test_grouped_ffn.py``'s cases of the grouped
expert kernel against ``ragged_dot`` and the dense reference: the kernel
rounds later than ``ragged_dot`` does, never earlier, and is no further from
the float32 answer."""

import jax.numpy as jnp
import pytest

from test_grouped_ffn import (CASES,
                              kernel_is_ragged_dot_and_the_dense_reference)


@pytest.mark.parametrize("dtype", [jnp.bfloat16], ids=["bfloat16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_is_ragged_dot_and_the_dense_reference(name, dtype,
                                                      monkeypatch):
    kernel_is_ragged_dot_and_the_dense_reference(name, dtype, monkeypatch)
