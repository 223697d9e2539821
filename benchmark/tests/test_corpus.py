"""The client corpus: its chunk size and its cache."""

import os

import numpy as np
import pytest

import corpus
from pair import seeded_hpke_keypair

CLIENT = {
    "task_id": bytes(32),
    "leader_hpke": seeded_hpke_keypair(5, 0).config.to_bytes(),
    "helper_hpke": seeded_hpke_keypair(5, 1).config.to_bytes(),
    "when": 1_600_000_000,
}


def _inst(vdaf):
    from janus_tpu.vdaf.registry import VdafInstance

    return VdafInstance.from_dict(vdaf)


@pytest.mark.parametrize(
    "vdaf, chunk",
    [({"kind": "count"}, 500), ({"kind": "sumvec", "length": 1000, "bits": 16}, 250)],
)
def test_client_chunk_divides_the_job(vdaf, chunk):
    assert corpus.client_chunk(_inst(vdaf), 500) == chunk


def test_seeded_hpke_keypair_opens_what_was_sealed_to_it():
    from janus_tpu.core.hpke import HpkeApplicationInfo, Label, hpke_open, hpke_seal
    from janus_tpu.messages import Role

    kp = seeded_hpke_keypair(2**40 + 3, 0)
    assert kp == seeded_hpke_keypair(2**40 + 3, 0)
    assert kp.config.public_key != seeded_hpke_keypair(2**40 + 4, 0).config.public_key
    info = HpkeApplicationInfo(Label.INPUT_SHARE, Role.CLIENT, Role.LEADER)
    ct = hpke_seal(kp.config, info, b"share", b"aad")
    assert hpke_open(kp, info, ct, b"aad") == b"share"


def test_cached_corpus_reads_back_what_it_made(tmp_path):
    vdaf = {"kind": "count"}
    args = (vdaf, _inst(vdaf), 9, 20, 20, 0.1, 20, CLIENT)
    made, hit = corpus.cached_corpus(str(tmp_path), *args)
    assert not hit and len(os.listdir(tmp_path)) == 1
    again, hit = corpus.cached_corpus(str(tmp_path), *args)
    assert hit
    assert again.reports == made.reports and again.report_ids == made.report_ids
    assert np.array_equal(again.measurements, made.measurements)
    assert np.array_equal(again.invalid, made.invalid)
    other, hit = corpus.cached_corpus(str(tmp_path), vdaf, _inst(vdaf), 10, 20, 20, 0.1, 20, CLIENT)
    assert not hit and other.reports != made.reports
