"""The conf-unread fixture's other module: what reads config.py's
entries.  A mention of UNREAD in a docstring or a comment is not a
read."""
from . import config as C


def enabled(settings):
    return settings.get(C.READ_BY_NAME[0], True)


def explain(settings):
    return settings.get("spark.rapids.sql.explain", "NONE")
