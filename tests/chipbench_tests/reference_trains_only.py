"""A ``reference`` module that only trains, made for the tests of the seam
(``test_chipbench_modules.py``): the dense decoder's layer list, weights,
tokens and training reference, and no ``served_gaps``. It lies here and
not under ``chipbench/`` because no configuration of the benchmark names
it; ``modules.load_file`` takes any Python file of the checkout.
"""
from chipbench.reference import (  # noqa: F401
    delta_norms, layer_list, leaf_norms, make_tokens, make_weights,
    train_reference)
