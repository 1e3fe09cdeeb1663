"""host.idle_unnamed_share: how much of the device's idle time the host's
stage timeline leaves unexplained.

`obs["concurrent"]["idle_gaps"]` (harness/trace.py `reduce`) names each idle
gap of the traced concurrent sub-window after the host event that overlaps it
most. Of the seconds of every gap but the short ones (under 50 us, one bucket
that no event is looked up for), the share named `titpu/exec` (the executor's
own code with no stage below it open), `host:no_traced_span` (no stage open
at all) or `device:gaps_not_looked_up` (past the harness's look-up limit).
Nothing to read (None) where the window has no such idle time.
"""

SHORT = "device:gaps_under_50us"
UNNAMED = ("titpu/exec", "host:no_traced_span", "device:gaps_not_looked_up")


def read(obs, spec):
    gaps = (obs.get("concurrent") or {}).get("idle_gaps") or ()
    idle = sum(s for name, s in gaps if name != SHORT)
    if not idle:
        return None
    return sum(s for name, s in gaps if name in UNNAMED) / idle
