from chipbench.tests.test_span_readers import *  # noqa
