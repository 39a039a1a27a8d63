"""A toy configuration module for the tests: what a deployment that
needs code of its own would put at benchmark/configs/<name>.py."""

CALLS = []


def after_preload(srv, cfg, cli):
    CALLS.append(("after_preload", cfg["name"]))


def reference_shard_files(body, cfg):
    """Not upstream's layout: shard i is its index byte, then the
    body's i-th stripe of every n bytes."""
    n = cfg["data_shards"] + cfg["parity_shards"]
    CALLS.append(("reference_shard_files", len(body)))
    return [bytes([i]) + body[i::n] for i in range(n)]
