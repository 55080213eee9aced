"""Query pipelines — the framework's "model zoo".

The reference framework's unit of deployment is a Spark query plan; these
modules are end-to-end pipelines (q6 = the TPC-H scan config, tpcds = the
served-SQL config), each a jittable scan→filter→aggregate program over the
columnar op library.
"""

from . import q6  # noqa: F401
