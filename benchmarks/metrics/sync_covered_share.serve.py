"""Share of the scheduler's blocking device reads that had work queued
behind what they waited for, in percent: over every ``serve.sync`` span
of the window, whatever its site, those whose ``ahead`` (the device
programs dispatched after the one whose output the read waits for) is at
least 1.  A read with ``ahead`` 0 leaves the device idle from the moment
it returns until the host has worked through its chain and dispatched
again; one with a decode step behind it costs the device nothing.  The
split by site goes to standard error.  A program whose ``serve.sync``
carries no ``ahead`` gives nothing."""


def read(run):
    import spans
    sp = spans.load(run)
    syncs = sp and sp.named("serve.sync")
    if not syncs or not any("ahead" in s.fields for s in syncs):
        return None
    by_site = {}
    for s in syncs:
        rec = by_site.setdefault(s.fields.get("site", "?"), [0, 0])
        rec[0] += s.fields.get("ahead", 0) >= 1
        rec[1] += 1
    covered = sum(c for c, _ in by_site.values())
    spans.note("sync_covered_share.serve",
               f"{covered} of {len(syncs)} blocking reads had work queued "
               "behind them; by site: " + ", ".join(
                   f"{site} {c} of {n}"
                   for site, (c, n) in sorted(by_site.items())))
    return 100.0 * covered / len(syncs)
