"""Bytes the nodes' chunk stores hold for a fixed slice of the cell's
stream, over the user bytes of that slice, replication included. The
guard on dedup when a chunker is changed.

The slice is the ``ratio_objects`` uploads that follow the first
``lead_objects`` of the stream (the traffic file's numbers; an upload's
key carries its number in the stream). Their bytes in the stores are the
files, on every node that has one, of every chunk their manifests name
and that no manifest of an earlier upload names: the harness's warm-up
object, the preload and the lead, with which the stream's repeated block
is already stored. Counted on disk once every client has stopped. It is
a count: of set-up's traffic nothing is in it, and it does not move with
how many uploads a window holds or when they were acked (which the same
bytes through the program's CPU engine confirm: PERF.md section 6). A
run so slow that part of the slice was never acked counts the part that
was, and says so.
"""


def read(w):
    lead = int(w.traffic["lead_objects"])
    last = lead + int(w.traffic["ratio_objects"])
    puts = [o for o in w.session_ops if o.kind == "put" and o.acked]
    inside = [o for o in puts if o.phase == "run" and lead <= o.key[1] < last]
    user = sum(o.nbytes for o in inside)
    if not user:
        return None
    if len(inside) < last - lead:
        print(f"[stored_ratio] only {len(inside)} of {last - lead} uploads "
              "of the slice were acked", flush=True)
    before = w.digests([o for o in puts
                        if o.phase != "run" or o.key[1] < lead])
    return w.bytes_on_disk(w.digests(inside) - before) / user
