"""The RSNN family, reached through its modules found by name, makes the
same weights, frames and reference logits, bit for bit, as the harness did
before families were found by name: sha256 digests at the CPU size of
``tiny``, written down from that harness."""

import functools
import hashlib

import jax
import numpy as np
import pytest

from bench.lib import harness, spec, traffic
from bench.tests import tiny

MIXES = ("timit_backlog", "timit_ptt_rate", "commands_backlog")
PRUNED_3 = {
    "params":
        "be25885a60cada8d1a7511fa47f4ab26709a33e859c6a3b1a0511409d65973ff",
    "frames.timit_backlog":
        "2da8fe06dc18a15ac8174820e875ae38f99dd601c98b079ed066acdeaac6d05c",
    "frames.timit_ptt_rate":
        "08557f2383eba9220ad67930f28700332e39794170be9d3c3fd5d0aace361db4",
    "frames.commands_backlog":
        "196b0280baa068c05a1d1580a058110e2bc2e408ba99402287d58a612bda16a2",
    "reference.highest":
        "d67a70bf9d3d952087afa930ef9495dcf617f32b2dc948e33da52b7e03ff91b8",
    "reference.high":
        "12653ebb7b809671024dbbb6bb4cb60de4928cbdd70858245d8eb739363b141d",
}
PRUNED_BIG = {
    "params":
        "d6fab99f25561321d2c719c51e9798623c7d4715a1b6f5b9824f53bfb3ad37a2",
    "frames.timit_backlog":
        "86d07e7704884e5faec6318319b1380daf27956886d1c6879a7d3278b612757c",
    "frames.timit_ptt_rate":
        "5070b56a77da542ff35134aed7f2f4418c433de70d1f84be8c47655ee38242c8",
    "frames.commands_backlog":
        "3bd132e67d68e359f8c2c8efef780d9b48bde6ad08e4b418da191a2dd21a6aa3",
    "reference.highest":
        "d0c1f6335a6c36b3c91cf9f3e3a948cd70ffbc35bed4f510660266b13922044c",
    "reference.high":
        "54f219ee7b02de1d35c31dc47442d6cf1df666ac61ba531baaea52f74830ec69",
}
# the tiny configurations share their widths, so only the reference, which
# serves the compression, differs between them
DIGESTS = {
    ("rsnn_pruned_int4_csc", 3): PRUNED_3,
    ("rsnn_pruned_int4_csc", 2**31 + 5): PRUNED_BIG,
    ("rsnn_baseline_f32", 3): dict(PRUNED_3, **{
        "reference.highest":
        "41de84d90a9d06a5f878a2acd883777153548c93f7068eebc37df7e51066fd81",
        "reference.high":
        "114d3eeebb375be5dfe0e2d11de48614abfb65f29ec402feb292b21535e437b5"}),
    ("rsnn_baseline_f32", 2**31 + 5): dict(PRUNED_BIG, **{
        "reference.highest":
        "d84e31040e9d51d11a55e08899e61665f443af317795f30a30cc3ab3f3d0f2eb",
        "reference.high":
        "568af69ac7c5b6bf22cfa78e4936c71e7f2343b890b11590a97d0a9505c2457f"}),
}


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def first_requests(plan: traffic.Plan, n: int = 16) -> list:
    if plan.traffic["loop"] == "open":
        return plan.arrivals[:n]
    reqs = list(plan.first)[:n]
    while len(reqs) < n:
        reqs.append(plan.next_pooled())
    return reqs


@pytest.mark.parametrize("name,seed", sorted(DIGESTS),
                         ids=lambda v: str(v))
def test_rsnn_weights_frames_and_reference_are_bit_identical(name, seed):
    conf = tiny.tiny_config(name)
    model = spec.Bench(tiny.ROOT).family(conf["reference"])
    cpu = jax.devices("cpu")[0]
    params = model.make_params(conf, seed, cpu)
    got = {"params": digest(jax.tree.leaves(params))}
    utts = None
    for mix in MIXES:
        plan = traffic.Plan(tiny.tiny_traffic(mix),
                            slots=conf["serving"]["slots"], seconds=2.0,
                            seed=seed,
                            make_bank=functools.partial(model.input_bank,
                                                        conf))
        frames = [plan.frames(r) for r in first_requests(plan)]
        got["frames." + mix] = digest(frames)
        utts = utts or frames
    for precision in ("highest", "high"):
        ref = harness.reference_logits(conf, params, utts, cpu, precision)
        got["reference." + precision] = digest(ref[i]
                                               for i in range(len(utts)))
    assert got == DIGESTS[(name, seed)]
