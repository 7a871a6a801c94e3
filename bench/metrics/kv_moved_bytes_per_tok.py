"""KV bytes moved between the tiers per output token: offload, reload,
disk spill and disk load, over the tokens emitted in the window."""


def read(run):
    tokens = run.output_tokens()
    if not tokens:
        return None
    moved = sum(run.delta(k) for k in ("offload_bytes", "reload_bytes",
                                       "disk_spill_bytes", "disk_load_bytes"))
    return moved / tokens
