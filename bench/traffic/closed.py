"""Closed loop: one client sends the mix's ``prologue`` requests, then its
``repeat`` requests round-robin (if any), each after the previous one is
answered, and stops at the first answer after ``--seconds`` or when the
requests run out.  A request's keys come from the seed and its place in
the sequence, so every run of a seed offers the same requests."""
import time
from contextlib import nullcontext
from typing import Iterator, List

from bench.traffic import Served, first_tenant, to_request


def requests(gen) -> Iterator:
    """The prologue, then the repeat entries round-robin, forever."""
    entries = list(gen.mix.get("prologue", []))
    repeat = gen.mix.get("repeat", [])
    i = 0
    while i < len(entries) or repeat:
        entry = entries[i] if i < len(entries) else repeat[
            (i - len(entries)) % len(repeat)]
        rng = gen.rng(1000 + i)
        yield gen.make(entry, rng, gen.pick_tenant(entry, rng))
        i += 1


def warm_requests(gen) -> List:
    """A whole request per entry that writes, so that set-up compiles
    every shape of the window's writes and flushes."""
    rng = gen.rng(2)
    return [gen.make(e, rng, first_tenant(e))
            for e in gen.mix.get("prologue", []) + gen.mix.get("repeat", [])
            if e["kind"] == "update"]


def settle(front, pl, ticket):
    """Block until the answer is in hand."""
    import jax
    front.drain(flush=pl.wait == "publish")
    if pl.wait == "publish":
        jax.block_until_ready(jax.tree.leaves(front.service.snapshot.cbl))
    if ticket.value is not None:
        return jax.block_until_ready(ticket.value)
    return None


def run(front, reqs: Iterator, seconds: float, clock=time.perf_counter,
        annotate=None) -> List[Served]:
    """Send each request after the previous one is answered; stop at the
    first answer after ``seconds``."""
    t0 = clock()
    out = []
    for pl in reqs:
        sent = clock()
        with annotate(f"bench.{pl.label}") if annotate else nullcontext():
            ticket = front.submit(to_request(pl))
            value = settle(front, pl, ticket)
        rec = Served(plan=pl, ticket=ticket, sent_t=sent, done_t=clock(),
                     value=value)
        out.append(rec)
        if rec.done_t - t0 >= seconds:
            break
    return out
