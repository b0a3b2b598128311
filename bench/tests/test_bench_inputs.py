"""The benchmark's tensor draw against the program's recipe."""

import numpy as np
import pytest

from bench import generators


@pytest.mark.parametrize("dims,nnz,zipf_a,seed", [((48, 40, 36), 900, 1.1, 1234),
                                                   ((64, 48, 80), 3000, 0.85, 0)])
def test_tensor_draw_is_the_program_recipe(dims, nnz, zipf_a, seed):
    from repro.core.sparse_tensor import random_sparse_tensor

    idx, val = generators.zipf_tensor(dims, nnz, zipf_a, seed)
    t = random_sparse_tensor(dims, nnz, seed=seed, zipf_a=zipf_a)
    np.testing.assert_array_equal(idx, t.indices)
    np.testing.assert_array_equal(val, t.values)
