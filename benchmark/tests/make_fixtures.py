#!/usr/bin/env python3
"""Build the two hand-made traces under ``benchmark/fixtures``.

    python3 benchmark/tests/make_fixtures.py

Times are microseconds here and nanoseconds in the file.  The numbers are
chosen so that every metric of the reduction can be worked out on paper; the
workings are in ``test_xplane.py`` beside the expected values.  The event
names follow what this runtime writes (an op event is named by its whole HLO
instruction line).

``one_chip_async.xplane.pb``: one chip, five two-step scanned dispatches
under a ``while``, a small second module between two of them, an
``Async XLA Ops`` line whose spans would fill the gaps if they were read, and
a host thread that names the two long gaps.

``four_chip.xplane.pb``: four chips with steps of different lengths, an
asynchronous all-reduce whose start and done operations sit on ``XLA Ops``
and whose in-flight span sits on ``Async XLA Ops``.
"""

from __future__ import annotations

import os

import xspace_writer as xw

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
US = 1000.0  # ns

WHILE = "%while.1 = (s32[], bf16[8,128]{1,0}) while(%tuple.4), " \
        "condition=%cond, body=%body"
FUSION = "%fusion.1 = (bf16[8192,2048]{1,0:T(8,128)(2,1)}, " \
         "f32[8192,2048]{1,0:T(8,128)}, f32[8192,2048]{1,0:T(8,128)}) " \
         "fusion(f32[8192,2048]{1,0} %p.1, bf16[8,2048,8192]{2,1,0} %p.2), " \
         "kind=kOutput, calls=%fused_computation.1"
FLASH = "%jvp_03-l0_att_.1 = (bf16[8,16,2048,128]{3,2,1,0:T(8,128)(2,1)}, " \
        "f32[8,16,2048]{2,1,0}) custom-call(bf16[8,16,2048,128]{3,2,1,0} " \
        '%bitcast.1, bf16[8,16,2048,128]{3,2,1,0} %bitcast.2), ' \
        'custom_call_target="tpu_custom_call"'
COPY = "%copy.3 = bf16[8,128]{0,1} copy(%fusion.1)"


def one_chip_async() -> bytes:
    starts = [100, 1300, 2500, 3700, 4900]
    durs = [1000, 1000, 1040, 1010, 1000]
    modules, ops, asyncs = [], [], []
    for s, d in zip(starts, durs):
        modules.append(("jit_run(123)", s * US, d * US))
        ops.append((WHILE, s * US, d * US))
        for half in (0, 500):
            ops += [(FUSION, (s + half + 10) * US, 300 * US),
                    (FLASH, (s + half + 320) * US, 100 * US),
                    (COPY, (s + half + 430) * US, 50 * US)]
        asyncs.append(("%copy-start.9 = (bf16[8,128]) copy-start(%p.3)",
                       s * US, 900 * US))
    modules.append(("jit_convert(7)", 2350 * US, 10 * US))
    ops.append(("%convert.1 = f32[8]{0} convert(%p.0)", 2350 * US, 10 * US))
    # in flight across the gaps between dispatches: never to be counted
    asyncs += [("%copy-start.9 = (bf16[8,128]) copy-start(%p.3)",
                2200 * US, 400 * US),
               ("%copy-start.9 = (bf16[8,128]) copy-start(%p.3)",
                3400 * US, 400 * US)]
    device = xw.plane(1, "/device:TPU:0", [
        ("XLA Modules", modules), ("XLA Ops", ops),
        ("Async XLA Ops", asyncs),
        ("Steps", [("0", 100 * US, 5800 * US)])])
    host = xw.plane(2, "/host:CPU", [
        ("main/4242", [("PjitFunction(run)", 2300 * US, 200 * US),
                       ("ReadLoss", 3540 * US, 160 * US)])])
    return [device, host]


def four_chip() -> bytes:
    planes = []
    for p in range(4):
        modules, ops, asyncs = [], [], []
        for s in (0, 1000, 2000, 3000, 4000):
            t = s + 3 * p
            modules.append(("jit_step(5)", t * US, (800 + 10 * p) * US))
            ops += [
                ("%fusion.7 = bf16[4096,4096]{1,0} fusion(%a), kind=kOutput",
                 t * US, 300 * US),
                ("%all-reduce-start.1 = f32[1024]{0} all-reduce-start(%g), "
                 "replica_groups={{0,1,2,3}}", (t + 300) * US, 10 * US),
                ("%fusion.8 = bf16[4096,4096]{1,0} fusion(%b), kind=kOutput",
                 (t + 310) * US, 190 * US),
                ("%all-reduce-done.1 = f32[1024]{0} all-reduce-done("
                 "%all-reduce-start.1)", (t + 500) * US, (60 + 10 * p) * US),
                ("%fusion.9 = bf16[4096,4096]{1,0} fusion(%c), kind=kLoop",
                 (t + 560 + 10 * p) * US, 220 * US)]
            asyncs.append(("%all-reduce-start.1 = f32[1024]{0} "
                           "all-reduce-start(%g)", (t + 300) * US,
                           (260 + 10 * p) * US))
        planes.append(xw.plane(p + 1, f"/device:TPU:{p}", [
            ("XLA Modules", modules), ("XLA Ops", ops),
            ("Async XLA Ops", asyncs)]))
    return planes


def main() -> None:
    os.makedirs(FIXTURES, exist_ok=True)
    xw.write(os.path.join(FIXTURES, "one_chip_async.xplane.pb"),
             one_chip_async())
    xw.write(os.path.join(FIXTURES, "four_chip.xplane.pb"), four_chip())
    for name in sorted(os.listdir(FIXTURES)):
        print(name, os.path.getsize(os.path.join(FIXTURES, name)), "bytes")


if __name__ == "__main__":
    main()
