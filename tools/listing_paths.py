#!/usr/bin/env python3
"""Count the bundles of every path round a kernel's scheduler loop.

Reads a ``*-final_bundles.txt`` listing of the v5e compiler
(``tools/kernel_listing.py`` dumps one, no chip) and walks its control
flow. What the count rests on (PR 45; ``PERF.md`` section 6):

- a ``sbr.rel`` at bundle X runs its four delay slots X+1..X+4 whichever
  way it goes, so a branch costs five bundles taken or not;
- its printed ``target bundleno`` is from an EARLIER numbering: the
  distinct targets, sorted, are matched in order to the listing's
  labelled lines (``LH:`` ``LB:`` ``LE:`` ``PB:`` ``PF:`` ``CT:``);
- the scheduler's loop is the WIDEST one: the loop whose back-branch
  lies farthest from its head holds every other loop of the kernel but
  those in front of and behind it (in ``--kernel search``'s listing a
  10-bundle loop that spills the lanes follows it, so "the last
  back-branch" named the wrong one; PR 50). ``--loop 0x<head>`` names
  another by its head.

Every syntactic path from the loop's head to its back-branch is listed
with its length and the way each branch went (``T`` taken, ``N`` not); an
inner loop is followed for at most three trips. Which of them a task
kind runs is read off the decisions (in the fib kernel: the first branch
after the pop tells SUM from FIB, the next a leaf from a fork, and so on).

A loop with batch bodies in it (``search``: sixteen copies of the relax
loop) has too many paths to list. ``--take 0x<branch>,...`` follows ONE:
from the loop's head, each named branch taken at its first visit (name it
twice to take it twice), every other one not, to the back-branch, and
prints that path's length and decisions. So the round that does nothing
in the ``search`` listing is ``--take`` of the three branches that jump
the starved phase's batch body, the drain phase's and the pop.

``--chain vrot.lane,...`` reads a loop whose cost is not its length but
what it WAITS for (the wavefront's sweep, ``kernel_listing.py --kernel
wave``; PR 54): it counts those operations in the loop named by ``--loop``
and the longest chain of them in which each takes a value the one before
it made, by definition and use through the loop's bundles in order (a
value the loop carries round counts from 0, memory is not followed). A
push to the cross-lane unit (``vrot.lane``, ``vadd.xlane``, ``vperm``) is
popped a latency later, so that chain times the latency is the least a
trip of the loop can take however short its listing.

    python tools/listing_paths.py <listing> [--count sdivrem,sand,spop]
                                  [--loop 0x<head>] [--take 0x<b>,0x<b>]
                                  [--chain vrot.lane,vadd.xlane]
"""

from __future__ import annotations

import argparse
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

DELAY_SLOTS = 4
MAX_TRIPS = 3
# The walk is exponential in the loop's branches: the fib kernel's loop has
# 8 branches and 27 paths, the resident mesh kernel's about a hundred
# branches and no end of paths (a first version walked it for 40 minutes).
# Past this many branch visits the walk stops and says so.
MAX_VISITS = 200_000

_BUNDLE = re.compile(
    r"^\s*(0x[0-9a-f]+|\d+)\s+(LH|LB|LE|PB|PF|CT)?:?\s*:?\s*>*\s*\{(.*)"
)
_TARGET = re.compile(r"target bundleno = (\d+)")
_BRANCH = re.compile(r"sbr\.rel \(([^)]*)\) target bundleno = (\d+)")


class Bundle(NamedTuple):
    addr: int
    label: str  # "" where the line carries none
    text: str


class Path(NamedTuple):
    bundles: int
    decisions: Tuple[Tuple[int, str, str], ...]  # (branch, predicate, T|N)


def parse(lines) -> List[Bundle]:
    """The listing's bundles in order; their addresses must count up
    from 0 without a gap (anything else is not a final-bundle listing)."""
    out = []
    for line in lines:
        m = _BUNDLE.match(line)
        if m:
            out.append(Bundle(int(m.group(1), 0), m.group(2) or "",
                              m.group(3)))
    if [b.addr for b in out] != list(range(len(out))):
        raise ValueError("bundle addresses do not count up from 0")
    return out


def branches(bundles: List[Bundle]) -> Dict[int, Tuple[str, int]]:
    """``{branch bundle: (predicate, target bundle)}``, the targets mapped
    from the printed numbering to this listing's labelled lines."""
    labels = [b.addr for b in bundles if b.label]
    targets = sorted(
        {int(t) for b in bundles for t in _TARGET.findall(b.text)}
    )
    if len(labels) != len(targets):
        raise ValueError(
            f"{len(labels)} labelled lines for {len(targets)} distinct "
            "branch targets: the label map cannot be matched in order"
        )
    tmap = dict(zip(targets, labels))
    out = {}
    for b in bundles:
        m = _BRANCH.search(b.text)
        if m:
            out[b.addr] = (m.group(1), tmap[int(m.group(2))])
    return out


def scheduler_loop(
    br: Dict[int, Tuple[str, int]], head: Optional[int] = None
) -> Tuple[int, int]:
    """``(head, back-branch)`` of the widest loop in the listing (the last
    of them where two are as wide), or of the loop whose head is
    ``head``, by its farthest back-branch."""
    backs = [a for a, (_, t) in br.items() if t < a]
    if not backs:
        raise ValueError("no backward branch: the listing has no loop")
    if head is not None:
        backs = [a for a in backs if br[a][1] == head]
        if not backs:
            raise ValueError(f"no loop has its head at {head:#x}")
    back = max(backs, key=lambda a: (a - br[a][1], a))
    return br[back][1], back


def follow(
    bundles: List[Bundle], take: Sequence[int], head: Optional[int] = None
) -> Path:
    """The one path from the loop's head to its back-branch on which each
    branch of ``take`` is taken, once for each time it is named, at its
    first visits, and every other branch is not."""
    br = branches(bundles)
    head, back = scheduler_loop(br, head)
    left = list(take)
    pc, n, dec = head, 0, ()
    while pc != back:
        if pc >= len(bundles) or n > len(bundles) * MAX_TRIPS:
            raise ValueError(
                f"the path left the loop {head:#x} .. {back:#x} at {pc:#x}"
            )
        if pc not in br:
            pc, n = pc + 1, n + 1
            continue
        pred, tgt = br[pc]
        taken = pc in left
        if taken:
            left.remove(pc)
        dec += ((pc, pred, "T" if taken else "N"),)
        n += 1 + DELAY_SLOTS
        pc = tgt if taken else pc + 1 + DELAY_SLOTS
    if left:
        raise ValueError(
            "not on the path: " + ", ".join(f"{a:#x}" for a in left)
        )
    return Path(n + 1 + DELAY_SLOTS, dec + ((back, br[back][0], "T"),))


def loop_paths(
    bundles: List[Bundle], max_visits: int = MAX_VISITS,
    head: Optional[int] = None,
) -> Tuple[int, int, List[Path], bool]:
    """``(head, back, paths, whole)``: every path from the scheduler
    loop's head round to its back-branch, shortest first; ``whole`` is
    False where the walk gave up after ``max_visits`` branch visits."""
    br = branches(bundles)
    head, back = scheduler_loop(br, head)
    end = len(bundles)
    paths: List[Path] = []
    visits = 0

    def walk(pc, n, dec, seen) -> None:
        nonlocal visits
        while pc < end and visits < max_visits:
            if pc not in br:
                pc += 1
                n += 1
                continue
            visits += 1
            pred, tgt = br[pc]
            trips = seen.get(pc, 0)
            if trips >= MAX_TRIPS:
                return
            seen = {**seen, pc: trips + 1}
            cost = 1 + DELAY_SLOTS
            if pc == back:
                # Taken closes the path; not taken leaves the loop.
                paths.append(Path(n + cost, dec + ((pc, pred, "T"),)))
                return
            walk(tgt, n + cost, dec + ((pc, pred, "T"),), seen)
            dec = dec + ((pc, pred, "N"),)
            pc += cost
            n += cost

    walk(head, 0, (), {})
    return head, back, sorted(paths), visits < max_visits


def count_ops(bundles: List[Bundle], mnemonic: str) -> int:
    """Operations of one mnemonic in the whole listing (``sdivrem``: an
    integer divide, which a ring index must not have; ``sand``; ``spop``)."""
    pat = re.compile(r"= " + re.escape(mnemonic) + r"\b")
    return sum(len(pat.findall(b.text)) for b in bundles)


_COMMENT = re.compile(r"/\*.*?\*/")
_OP = re.compile(r"^(%\w+) = ([\w.]+)(.*)$")
_VALUE = re.compile(r"%\w+")


def chain_depth(
    bundles: List[Bundle], head: int, back: int, mnemonics: Sequence[str]
) -> Tuple[int, int]:
    """``(operations, longest dependent chain)`` of the operations whose
    name starts with one of ``mnemonics`` in the loop ``head .. back`` and
    its delay slots."""
    depth: Dict[str, int] = {}
    ops = 0
    for b in bundles[head:back + 1 + DELAY_SLOTS]:
        made = {}
        for op in _COMMENT.sub("", b.text).split(";;"):
            m = _OP.match(op.strip().rstrip("}").strip())
            if not m:
                continue
            counted = m.group(2).startswith(tuple(mnemonics))
            ops += counted
            made[m.group(1)] = counted + max(
                (depth.get(v, 0) for v in _VALUE.findall(m.group(3))),
                default=0,
            )
        depth.update(made)  # a bundle's operations read what stood before it
    return ops, max(depth.values(), default=0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("listing")
    ap.add_argument("--count", default="sdivrem,sand,spop",
                    help="mnemonics to count, comma-separated")
    ap.add_argument("--loop", type=lambda x: int(x, 0), default=None,
                    help="head bundle of the loop to walk (default: the "
                    "widest loop)")
    ap.add_argument("--take", default=None,
                    help="follow one path: these branches taken, "
                    "comma-separated, every other one not")
    ap.add_argument("--chain", default=None,
                    help="count these operations in the loop and the "
                    "longest dependent chain of them, comma-separated")
    a = ap.parse_args(argv)
    with open(a.listing) as f:
        bundles = parse(f)
    head, back = scheduler_loop(branches(bundles), a.loop)
    print(f"kernel {len(bundles)} bundles; loop {head:#x} .. {back:#x}, "
          f"{back + 1 + DELAY_SLOTS - head} bundles")
    for name in filter(None, a.count.split(",")):
        print(f"{name} {count_ops(bundles, name)}")
    if a.chain is not None:
        ops, chain = chain_depth(bundles, head, back, a.chain.split(","))
        print(f"{a.chain}: {ops} in the loop, {chain} in series")
        return 0
    if a.take is not None:
        paths, whole = [follow(
            bundles, [int(x, 0) for x in a.take.split(",") if x], a.loop
        )], True
    else:
        *_, paths, whole = loop_paths(bundles, head=a.loop)
    for p in paths:
        print(p.bundles,
              " ".join(f"{b:#x}:{pred}={d}" for b, pred, d in p.decisions))
    if not whole:
        print(f"gave up after {MAX_VISITS} branch visits: the loop has too "
              "many branches to list every path; these are some of them")
    return 0


if __name__ == "__main__":
    sys.exit(main())
