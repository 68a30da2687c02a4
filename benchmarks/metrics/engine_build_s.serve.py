"""``setup.engine_init``: the time ``ServingEngine()`` took, warm-up
included, in seconds.  On standard error: its children (state snapshot,
cache allocation, warm-up) and the first scheduler iteration with its
blocking reads, which is where a transfer that ``__init__`` did not wait
for shows."""


def read(run):
    import spans
    sp = spans.load(run, "setup")
    init = sp and sp.named("setup.engine_init")
    if not init:
        return None
    init = init[0]
    parts = [f"{c.name} {spans.ms(c) / 1e3:.2f} s"
             + (f" ({c.fields['bytes'] / 1e9:.2f} GB)"
                if "bytes" in c.fields else "")
             for c in sorted(sp.children(init), key=lambda c: c.start_ns)]
    first = sp.named("serve.step")
    if first:
        syncs = [f"{c.fields.get('site')} {spans.ms(c) / 1e3:.2f} s"
                 for c in sp.descendants(first[0]) if c.name == "serve.sync"]
        parts.append(f"first serve.step {spans.ms(first[0]) / 1e3:.2f} s "
                     f"(syncs: {', '.join(syncs) or 'none'})")
    spans.note("engine_build_s.serve", "; ".join(parts))
    return spans.ms(init) / 1e3
