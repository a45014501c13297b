"""Mean megabytes (1e6 bytes) of cache that each execute's decode loop
carried, KV and recurrent state together: ``kv_bytes`` plus
``state_bytes`` of the window's ``inv.execute`` spans (endpoint layer).
None where the program records no such counter."""
from harness import program


def read(ctx):
    invs = program.invocations(ctx)
    if invs is None:
        return None
    got = [s.attrs["kv_bytes"] + s.attrs["state_bytes"]
           for s in (g["inv.execute"] for g in invs.values())
           if "kv_bytes" in s.attrs and "state_bytes" in s.attrs]
    return sum(got) / len(got) / 1e6 if got else None
