from chipbench.tests.test_strings_cell import *  # noqa
