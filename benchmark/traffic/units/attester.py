"""Request unit "attester": `count` unaggregated attestations a request,
each one set of one pubkey: a member of a committee of the epoch's
first slot signing its committee's attestation root, in
committee-interleaved order (member j of every committee before member
j + 1 of any), as the slot's gossip reaches a node subscribed to every
subnet."""

KEYS = {"count"}


def requests(config, dep, msgs, params, n):
    per = int(params["count"])
    comms = dep.committees(0)
    stream = []
    for j in range(max(len(c) for c in comms)):
        for c, members in enumerate(comms):
            if j < len(members):
                stream.append(([int(members[j])], msgs(("att", 0, c))))
    need = per * n
    if need > len(stream):
        raise ValueError(f"{need} attestations asked of a slot of "
                         f"{len(stream)}")
    return [stream[i:i + per] for i in range(0, need, per)]
