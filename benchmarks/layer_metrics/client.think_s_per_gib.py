"""client: seconds the closed loop's clients spent between one upload's
ack and the next one's connect — making the next object from the seed,
hashing it for the id the node must answer — summed over the clients,
inside the window, per GiB acked in it (the harness's own op log; host
clock). Load the cell did not offer: with 3 clients, this over
``edge.upload_s_per_gib`` is the share of the loop the system sat
unasked."""


def read(w):
    by_client: dict[int, list] = {}
    for o in w.session_ops:
        if o.kind == "put" and o.phase == "run":
            by_client.setdefault(o.client, []).append(o)
    idle = 0.0
    for ops in by_client.values():
        ops.sort(key=lambda o: o.t0)
        for done, nxt in zip(ops, ops[1:]):
            idle += max(0.0, min(nxt.t0, w.t_close) - max(done.t1, w.t_open))
    return w.per_gib_put(idle)
