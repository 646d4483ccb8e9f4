"""The benchmark's tests run every cell of the manifest at the tiny sizes of
``tiny.py``'s table, by family. A family that came after the table brings its
own sizes (``families/<family>.py::TINY``); they are put into the table here,
for the tests alone."""

from benchmark.families import lm_hybrid_ssm_moe_train, lm_sparse_moe_train
from benchmark.tests import tiny

tiny.TINY.setdefault("lm_sparse_moe_train", lm_sparse_moe_train.TINY)
tiny.TINY.setdefault("lm_hybrid_ssm_moe_train", lm_hybrid_ssm_moe_train.TINY)
