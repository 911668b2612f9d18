"""conf-unread rule fixture: a ConfEntry bound to a module-level name
in a `config.py` must be read somewhere in that file's package (this
directory: `reader.py` is the package's only other module).  The keys
are registered ones, so `conf-discipline` stays quiet here."""


def conf(key, default, doc):
    return (key, default, doc)


READ_BY_NAME = conf("spark.rapids.sql.enabled", True, "read as C.NAME")
READ_BY_KEY = conf("spark.rapids.sql.explain", "NONE", "read by its key")
READ_HERE = conf("spark.rapids.sql.batchSizeBytes", 1, "read below")
UNREAD = conf("spark.rapids.sql.incompatibleOps.enabled", False,  # EXPECT: conf-unread
              "registered, documented, read by nothing")
NOT_AN_ENTRY = 7                                         # not conf(): fine


def batch_bytes(settings):
    return settings.get(READ_HERE[0], READ_HERE[1])
