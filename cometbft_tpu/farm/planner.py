"""The farm's planning: light/planner.py's commit planner and schedule
planner (`plan_update`, `VerifyStep`, the decision records) under the
farm's SigCache label; its fetch budget is `farm/service.py`'s. Why a
threshold tally never needs the device, and why the planner's verdicts
equal a LightClient's lane for lane, is said there.

So a whole bisection schedule costs only provider fetches and hashing;
the signature lanes it emits are verified LATER, coalesced with every
other session's lanes in one shared device batch (batcher.py).
"""

from __future__ import annotations

import functools

from ..light import planner as light_planner
from ..light.planner import (Lane, PlannedCheck, VerifyStep,  # noqa: F401
                             _add_lane)
from ..light.verifier import PlanBudgetExceeded  # noqa: F401

# SigCache attribution label for farm lanes; the light client's own
# lanes, planned by the same light/planner.py, are labelled "light"
CACHE_PATH = "farm"

plan_commit_light = functools.partial(light_planner.plan_commit_light,
                                      path=CACHE_PATH)
plan_commit_trusting = functools.partial(
    light_planner.plan_commit_trusting, path=CACHE_PATH)
plan_update = functools.partial(light_planner.plan_update, path=CACHE_PATH)
