"""``tools/listing_paths.py``: the path counter two PRs' cost model rests
on (PR 45, PR 46), on a small hand-written listing in the v5e compiler's
final-bundle format: a prologue, a scheduler loop with a taken and an
untaken ``sbr.rel``, an inner loop, delay slots, and printed branch
targets from an earlier numbering that only the label map resolves."""

import os
import sys

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
)
import listing_paths as lp  # noqa: E402

# Bundles 0x0-0x2 prologue; 0x3 the scheduler loop's head (LB); a branch at
# 0x4 that jumps the 6-bundle block 0x9-0xe to the PF at 0xf; an inner loop
# 0x10-0x15 (LB at 0x10, back-branch at 0x11); the outer back-branch at
# 0x17. Printed targets 40 < 55 < 70 are NOT bundle numbers: sorted, they
# map to the labelled lines 0x3, 0xf, 0x10 in order.
LISTING = """\
= control target key start
LB: loop body
PF: predicated region fallthrough
= control target key end

     0   :  { %1 = vsyncpa [#allocation3], 0 }
   0x1   :  { %s2 = sld [smem:[#allocation2]] }
   0x2   :  {}
   0x3 LB: > { %s10 = sld [smem:[#allocation4]]  ;;  %s11 = sand.u32 1023, %s2 }
   0x4   : > { %20 = sbr.rel (%p5_p0) target bundleno = 55 (0x37), region = 12 }
   0x5   : > { %s12 = sadd.s32 1, %s10 }
   0x6   :  {}
   0x7   :  {}
   0x8   :  {}
   0x9   : > { %s13 = sdivrem.u32 %s12, 768 }
   0xa   : > { %s14 = sand.u32 (!%p5_p0), 1023, %s13 }
   0xb   : > { %s15 = sld [smem:[#allocation4 + $0x1]] }
   0xc   : > { %16 = sst [smem:[#allocation4]] %s15 }
   0xd   :  {}
   0xe   :  {}
   0xf PF: > { %s17 = sld [smem:[#allocation4 + $0x3]] }
  0x10 LB: >> { %s18 = sadd.s32 1, %s17 }
  0x11   : >> { %30 = sbr.rel (!%p6_p1) target bundleno = 70 (0x46), region = 20 }
  0x12   :  {}
  0x13   :  {}
  0x14   :  {}
  0x15   :  {}
  0x16   : > { %p7_p2 = scmp.gt.s32.totalorder %s18, 0 }
  0x17   :  { %40 = sbr.rel (!%p7_p2) target bundleno = 40 (0x28), region = 30 }
  0x18   :  {}
  0x19   :  {}
  0x1a   :  {}
  0x1b   :  {}
  0x1c PF:  { %s50 = sld [smem:[#allocation2]] }
"""


@pytest.fixture(scope="module")
def bundles():
    return lp.parse(LISTING.splitlines())


def test_parse_keeps_every_bundle_and_its_label(bundles):
    assert len(bundles) == 0x1d
    assert [b.addr for b in bundles if b.label] == [0x3, 0xf, 0x10, 0x1c]
    assert bundles[0x3].label == "LB" and bundles[0xf].label == "PF"
    assert bundles[0x6].text.strip() == "}"  # an empty delay slot


def test_a_gap_in_the_addresses_is_not_a_listing():
    lines = [ln for ln in LISTING.splitlines() if not ln.startswith("   0x7")]
    with pytest.raises(ValueError, match="count up"):
        lp.parse(lines)


def test_printed_targets_resolve_through_the_label_map_in_order(bundles):
    # A fourth label (0x1c, the loop's exit) has no branch to it: the map
    # is matched label for target, so it must refuse rather than shift.
    with pytest.raises(ValueError, match="4 labelled lines for 3"):
        lp.branches(bundles)
    br = lp.branches(bundles[:0x1c])
    assert br == {
        0x4: ("%p5_p0", 0xf),
        0x11: ("!%p6_p1", 0x10),
        0x17: ("!%p7_p2", 0x3),
    }
    assert lp.scheduler_loop(br) == (0x3, 0x17)


@pytest.mark.parametrize(
    "decisions,length",
    [
        # head 0x3, branch 0x4 + 4 delay slots, jump to 0xf: 1 + 5; then
        # 0xf, 0x10, inner branch 0x11 untaken + slots: 2 + 5; 0x16; the
        # back-branch + slots: 1 + 5.
        ("0x4=T 0x11=N 0x17=T", 19),
        # untaken at 0x4: the six bundles 0x9-0xe more.
        ("0x4=N 0x11=N 0x17=T", 25),
        # one more trip of the inner loop: 0x10, 0x11 + slots = 6 more.
        ("0x4=T 0x11=T 0x11=N 0x17=T", 25),
        ("0x4=N 0x11=T 0x11=N 0x17=T", 31),
        ("0x4=T 0x11=T 0x11=T 0x11=N 0x17=T", 31),
        ("0x4=N 0x11=T 0x11=T 0x11=N 0x17=T", 37),
    ],
)
def test_every_path_round_the_loop_with_its_length(bundles, decisions, length):
    head, back, paths, whole = lp.loop_paths(bundles[:0x1c])
    assert (head, back, whole) == (0x3, 0x17, True)
    assert len(paths) == 6  # an inner loop is followed for three trips
    got = {
        " ".join(f"{b:#x}={d}" for b, _, d in p.decisions): p.bundles
        for p in paths
    }
    assert got[decisions] == length
    assert [p.bundles for p in paths] == sorted(p.bundles for p in paths)


def test_a_walk_that_runs_out_of_visits_says_so(bundles):
    *_, paths, whole = lp.loop_paths(bundles[:0x1c], max_visits=4)
    assert not whole and len(paths) < 6


def test_mnemonics_are_counted_by_operation_not_by_line(bundles):
    assert lp.count_ops(bundles, "sdivrem") == 1
    assert lp.count_ops(bundles, "sand") == 2
    assert lp.count_ops(bundles, "spop") == 0
    assert lp.count_ops(bundles, "sld") == 5


def test_the_command_line_prints_the_loop_the_counts_and_the_paths(
    tmp_path, capsys
):
    f = tmp_path / "k-70-final_bundles.txt"
    f.write_text("\n".join(LISTING.splitlines()[:-1]) + "\n")
    assert lp.main([str(f), "--count", "sdivrem,sand"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0] == "kernel 28 bundles; loop 0x3 .. 0x17, 25 bundles"
    assert out[1:3] == ["sdivrem 1", "sand 2"]
    assert out[3] == "19 0x4:%p5_p0=T 0x11:!%p6_p1=N 0x17:!%p7_p2=T"
    assert len(out) == 3 + 6


# ISSUE 50: the ``search`` kernel's listing has a 10-bundle loop BEHIND the
# scheduler's (the exit's spill of the lanes), so "the last back-branch"
# named the wrong loop: the scheduler's is the widest. The same listing
# with such a loop at 0x1c .. 0x1d behind the scheduler's.
TRAILED = "\n".join(
    LISTING.splitlines()[:-1]
    + [
        "  0x1c LB: > { %s50 = sld [smem:[#allocation2]] }",
        "  0x1d   : > { %60 = sbr.rel (!%p9_p3) target bundleno = 90 "
        "(0x5a), region = 40 }",
        "  0x1e   :  {}",
        "  0x1f   :  {}",
        "  0x20   :  {}",
        "  0x21   :  {}",
    ]
)


@pytest.fixture(scope="module")
def trailed():
    return lp.parse(TRAILED.splitlines())


@pytest.mark.parametrize(
    "head,loop",
    [
        (None, (0x3, 0x17)),   # the widest, not the last
        (0x1c, (0x1c, 0x1d)),  # --loop names another by its head
        (0x10, (0x10, 0x11)),
    ],
)
def test_the_scheduler_loop_is_the_widest_or_the_one_named(
    trailed, head, loop
):
    br = lp.branches(trailed)
    assert max(a for a, (_, t) in br.items() if t < a) == 0x1d
    assert lp.scheduler_loop(br, head) == loop
    assert lp.loop_paths(trailed, head=head)[:2] == loop


def test_a_head_that_no_loop_has_is_refused(trailed):
    with pytest.raises(ValueError, match="no loop has its head at 0x5"):
        lp.scheduler_loop(lp.branches(trailed), 0x5)


@pytest.mark.parametrize(
    "take,decisions,length",
    [
        ([], "0x4=N 0x11=N 0x17=T", 25),
        ([0x4], "0x4=T 0x11=N 0x17=T", 19),
        ([0x11, 0x4, 0x11], "0x4=T 0x11=T 0x11=T 0x11=N 0x17=T", 31),
    ],
)
def test_one_path_followed_by_the_branches_it_takes(
    trailed, take, decisions, length
):
    p = lp.follow(trailed, take)
    assert p.bundles == length
    assert " ".join(f"{b:#x}={d}" for b, _, d in p.decisions) == decisions
    # the walk of every path finds the same one
    assert p in lp.loop_paths(trailed)[2]


def test_a_branch_named_that_the_path_never_meets_is_refused(trailed):
    with pytest.raises(ValueError, match="not on the path: 0x1d"):
        lp.follow(trailed, [0x4, 0x1d])


def test_the_command_line_follows_one_path_of_a_named_loop(tmp_path, capsys):
    f = tmp_path / "k-71-final_bundles.txt"
    f.write_text(TRAILED + "\n")
    assert lp.main([str(f), "--count", "", "--take", "0x4"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out == [
        "kernel 34 bundles; loop 0x3 .. 0x17, 25 bundles",
        "19 0x4:%p5_p0=T 0x11:!%p6_p1=N 0x17:!%p7_p2=T",
    ]
    assert lp.main([str(f), "--count", "", "--loop", "0x1c"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out == [
        "kernel 34 bundles; loop 0x1c .. 0x1d, 6 bundles",
        "6 0x1d:!%p9_p3=T",
    ]


# A sweep-like loop 0x1..0xa (back-branch at 0x6, its delay slots to 0xa):
# two rolls pushed side by side on a value the loop carries round, a third
# on what both made, a lane sum that depends on none of them, and a roll
# in the delay slots on the third's result. Comments may name values: they
# are not uses.
CHAIN = """\
     0   :  { %v1_v0 = vld [vmem:[#allocation2] sm:$0xff] }
   0x1 LB: > { %10 = vrot.lane.b32.xlu0 %v9_v1, %s2  ;;  %11 = vrot.lane.b32.xlu1 %v9_v1, %s3 }
   0x2   : > { %v12_v2 = vpop.permute.xlu0 %10  ;;  %v13_v3 = vpop.permute.xlu1 %11 }
   0x3   : > { %20 = vadd.xlane.f32.xlu2 %v1_v0  ;;  %v9_v1 = vphi %v1_v0, %v30_v5 /* phi */ }
   0x4   : > { %v14_v4 = vsel /*vm=*/%vm5_vm0, %v12_v2, /*x=*/%v13_v3 /* not %v31_v6 */ }
   0x5   : > { %15 = vrot.lane.b32.xlu0 %v14_v4, %s2_s0 }
   0x6   : > { %40 = sbr.rel (!%p6_p1) target bundleno = 9 (0x9), region = 3 }
   0x7   : > { %v16_v5 = vpop.permute.xlu0 %15  ;;  %v21_v7 = vpop.xlane.xlu2 %20 }
   0x8   : > { %v30_v5 = vmax.s32 %v16_v5, %v21_v7 }
   0x9   : > { %31 = vrot.lane.b32.xlu1 (%p6_p1), %v30_v5, %s2_s0 }
   0xa   : > { %v31_v6 = vpop.permute.xlu1 %31 }
   0xb   :  { %50 = vst [vmem:[#allocation3] sm:$0xff] %v31_v6 }
"""


@pytest.mark.parametrize("mnemonics,want", [
    (["vrot.lane"], (4, 3)),  # 10 | 11 -> 15 -> 31
    (["vadd.xlane"], (1, 1)),
    (["vrot.lane", "vadd.xlane"], (5, 3)),  # the lane sum joins at depth 1
    (["vrot", "vadd"], (5, 3)),  # a mnemonic is a prefix
    (["vperm"], (0, 0)),
])
def test_chain_depth_follows_definitions_and_uses(mnemonics, want):
    bundles = lp.parse(CHAIN.splitlines())
    head, back = lp.scheduler_loop(lp.branches(bundles))
    assert (head, back) == (1, 6)
    assert lp.chain_depth(bundles, head, back, mnemonics) == want


def test_chain_option_prints_the_count_and_the_chain(tmp_path, capsys):
    path = tmp_path / "chain-final_bundles.txt"
    path.write_text(CHAIN)
    assert lp.main([str(path), "--chain", "vrot.lane"]) == 0
    assert capsys.readouterr().out.splitlines()[-1] == (
        "vrot.lane: 4 in the loop, 3 in series")
