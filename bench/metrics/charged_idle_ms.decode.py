"""Mean time, ms, in which the chip ran nothing inside a charged decode
step: the program's ``live.decode`` spans (``live/recorder.py``
``CostLedger.charge``, around exactly the interval that becomes
simulated time), each one's device-idle time, averaged over them."""

import progtrace


def read(ctx):
    p = progtrace.of(ctx)
    idle = p.idle_in("livestack.live.decode") if p is not None else []
    if not idle:
        return None
    return sum(idle) / len(idle) * 1e-6
