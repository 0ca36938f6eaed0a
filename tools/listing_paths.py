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
- the scheduler's loop is the ``LB:`` whose back-branch comes last.

Every syntactic path from the loop's head to its back-branch is listed
with its length and the way each branch went (``T`` taken, ``N`` not); an
inner loop is followed for at most three trips. Which of them a task
kind runs is read off the decisions (in the fib kernel: the first branch
after the pop tells SUM from FIB, the next a leaf from a fork, and so on).

    python tools/listing_paths.py <listing> [--count sdivrem,sand,spop]
"""

from __future__ import annotations

import argparse
import re
import sys
from typing import Dict, List, NamedTuple, Tuple

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


def scheduler_loop(br: Dict[int, Tuple[str, int]]) -> Tuple[int, int]:
    """``(head, back-branch)`` of the last loop in the listing."""
    backs = [a for a, (_, t) in br.items() if t < a]
    if not backs:
        raise ValueError("no backward branch: the listing has no loop")
    back = max(backs)
    return br[back][1], back


def loop_paths(
    bundles: List[Bundle], max_visits: int = MAX_VISITS
) -> Tuple[int, int, List[Path], bool]:
    """``(head, back, paths, whole)``: every path from the scheduler
    loop's head round to its back-branch, shortest first; ``whole`` is
    False where the walk gave up after ``max_visits`` branch visits."""
    br = branches(bundles)
    head, back = scheduler_loop(br)
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("listing")
    ap.add_argument("--count", default="sdivrem,sand,spop",
                    help="mnemonics to count, comma-separated")
    a = ap.parse_args(argv)
    with open(a.listing) as f:
        bundles = parse(f)
    head, back, paths, whole = loop_paths(bundles)
    print(f"kernel {len(bundles)} bundles; loop {head:#x} .. {back:#x}, "
          f"{back + 1 + DELAY_SLOTS - head} bundles")
    for name in filter(None, a.count.split(",")):
        print(f"{name} {count_ops(bundles, name)}")
    for p in paths:
        print(p.bundles,
              " ".join(f"{b:#x}:{pred}={d}" for b, pred, d in p.decisions))
    if not whole:
        print(f"gave up after {MAX_VISITS} branch visits: the loop has too "
              "many branches to list every path; these are some of them")
    return 0


if __name__ == "__main__":
    sys.exit(main())
