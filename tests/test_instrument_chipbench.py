from chipbench.tests.test_chipbench import *  # noqa
