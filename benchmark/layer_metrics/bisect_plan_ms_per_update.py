"""`bisect_plan_ms_per_update.*`: median of the program's
`light.bisect.plan` spans (light/client.py `_verify_skipping`), one an
update of the skipping light client, host clock, in ms; count printed on
an earlier line. From the first pivot fetched to the schedule's end:
the fetches from the provider, `validate_basic`, the checks that need no
signature, both checks of every step planned (sign-bytes and cache
lookups of the lanes they take). Nothing to read where the program opens
no such span (before the planned skipping walk)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "light.bisect.plan")
