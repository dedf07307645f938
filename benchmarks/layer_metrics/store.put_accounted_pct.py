"""chunk store: the ten phases of the put jobs (``durability.put``,
PR 38) as a share of the jobs' whole (``jobS``) over the window, the
nodes together: 100 when every instant of a job is in a phase. Under
95, a phase is missing from the clock. Nothing on a program without
the phase clock, or where no job returned."""

from program_totals import share_pct
from put_phases import PHASES, put_delta


def read(w):
    return share_pct(put_delta(w, *PHASES), put_delta(w, "jobS"))
