from chipbench.tests.test_compile_ledger_reader import *  # noqa
