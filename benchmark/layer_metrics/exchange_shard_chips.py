"""Layer: exchange.  The least number of chips that held a shard of
any mesh exchange's output (`ShuffleExchangeExec._MESH_SHARD_DEVICES`).
Nothing to read where no exchange took the mesh lane."""


def read(ctx):
    held = ctx["shard_devices"]
    return min(len(ids) for ids in held) if held else None
