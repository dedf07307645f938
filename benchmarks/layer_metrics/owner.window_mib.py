"""owner seam and device walk: the mean device window of the streamed
walks (``Health.device`` ``windowBytes`` over ``windows``, over the
window; a window's ``end - base``, counted at its collect; a packed
region is no window), in MiB: 16 where an upload is one 16 MiB window,
60.4 where a 1 GiB stream is 16 full 64 MiB windows and a 2 MiB tail,
56.9 where a 512 MiB stream is 8 and a 1 MiB tail.
Nothing on a program without the counters."""

from program_totals import owner_s
from window import MIB


def read(w):
    nbytes, windows = owner_s(w, "windowBytes"), owner_s(w, "windows")
    return nbytes / windows / MIB if windows else None
