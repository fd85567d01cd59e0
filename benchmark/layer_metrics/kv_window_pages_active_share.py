"""Cache manager: pages the window group's running sequences hold over what
the same sequences would hold with every layer whole
(`dynamo_engine_kv_group_pages{group="window"}` active / whole), the mean of
the once-a-second samples: what giving back the pages behind a window saves."""
import probe


def read(ctx):
    shares = []
    for _, table in ctx["samples"]:
        active = probe.sample(table, "dynamo_engine_kv_group_pages", group="window", state="active")
        whole = probe.sample(table, "dynamo_engine_kv_group_pages", group="window", state="whole")
        if active is not None and whole:
            shares.append(100.0 * active / whole)
    return sum(shares) / len(shares) if shares else None
