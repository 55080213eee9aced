from chipbench.tests.test_cabi_cell import *  # noqa
