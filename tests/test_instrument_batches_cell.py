from chipbench.tests.test_batches_cell import *  # noqa
