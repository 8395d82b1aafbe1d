"""TLS on the TCP transports (DESIGN.md §9): mutual-TLS wrapping of
both framings, clean attributed failures for misconfigured peers (no
hang-to-timeout), and bit-identity of TLS'd depth-1 runs with the
recorded seed traces — including composed with WAN link shaping and
encode offload (the snapshot contract)."""
import json
import pathlib
import time

import numpy as np
import pytest

from repro.comm.base import CommCfg, LinkSpec, TLSSpec
from repro.comm.grpc import GrpcCommunicator
from repro.comm.sock import SocketCommunicator, local_addresses
from repro.core.party import run_vfl
from repro.core.protocols.base import VFLConfig
from repro.data.vertical import vertical_partition
from repro.launch.certs import TestCA, have_openssl

pytestmark = pytest.mark.skipif(
    not have_openssl(), reason="openssl CLI required to mint test certs")

TRACES = json.loads(
    (pathlib.Path(__file__).parent / "fixtures" / "seed_traces.json")
    .read_text())


@pytest.fixture(scope="session")
def certs(tmp_path_factory):
    ca = TestCA(tmp_path_factory.mktemp("certs"))
    for n in ("a", "b", "master", "member0", "member1"):
        ca.issue(n)
    return ca


@pytest.fixture(scope="session")
def other_ca(tmp_path_factory):
    ca = TestCA(tmp_path_factory.mktemp("certs2"))
    ca.issue("a")
    return ca


def _pair(cls, cfg_a, cfg_b):
    addrs = local_addresses(["a", "b"])
    return cls("a", addrs, comm_cfg=cfg_a), cls("b", addrs,
                                                comm_cfg=cfg_b)


@pytest.mark.parametrize("cls, pairs, timeout", [
    pytest.param(SocketCommunicator, 1, 20.0, id="SocketCommunicator"),
    pytest.param(GrpcCommunicator, 1, 20.0, id="GrpcCommunicator"),
    # the gRPC framing's reader and sender threads once met inside one
    # SSL object and lost a fresh connection's first message now and then
    pytest.param(GrpcCommunicator, 24, 4.0,
                 id="GrpcCommunicator-24-fresh-pairs"),
])
def test_tls_roundtrip_both_framings(cls, pairs, timeout, certs):
    cfg = CommCfg(timeout=timeout, tls=certs.templated_spec())
    for _ in range(pairs):
        ca_, cb = _pair(cls, cfg, cfg)
        try:
            cb.send("a", "t", {"x": np.arange(4.0)})
            msg = ca_.recv("b", "t")
            np.testing.assert_array_equal(msg.tensor("x"), np.arange(4.0))
            ca_.send("b", "r", {"x": np.ones(2)}, meta={"k": "v"})
            assert cb.recv("a", "r").meta["k"] == "v"
        finally:
            ca_.close()
            cb.close()


def test_wrong_ca_fails_fast_with_peer_attribution(certs, other_ca):
    """An untrusted server certificate must surface as an immediate
    ConnectionError naming the peer — not a retry loop or a hang."""
    good = CommCfg(timeout=30.0, tls=certs.templated_spec())
    # client trusts the WRONG CA: server cert verification fails
    bad = CommCfg(timeout=30.0, tls=TLSSpec(
        cert=str(certs.dir / "a.crt"), key=str(certs.dir / "a.key"),
        ca=other_ca.ca_cert))
    ca_, cb = _pair(SocketCommunicator, bad, good)
    try:
        t0 = time.monotonic()
        with pytest.raises(ConnectionError) as ei:
            ca_.send("b", "t", {"x": np.zeros(1)})
        assert time.monotonic() - t0 < 10.0      # no hang-to-timeout
        assert "'b'" in str(ei.value)
        assert "TLS handshake" in str(ei.value)
    finally:
        ca_.close()
        cb.close()


def test_plaintext_client_rejected_by_tls_server(certs):
    """A plaintext client against a TLS server must get a clean
    ConnectionError, not a silent hang: the server drops the
    connection when the hello frame fails the TLS handshake."""
    srv_cfg = CommCfg(timeout=20.0, tls=certs.templated_spec())
    addrs = local_addresses(["a", "b"])
    srv = SocketCommunicator("b", addrs, comm_cfg=srv_cfg)
    cli = SocketCommunicator("a", addrs, timeout=10.0)   # no TLS
    try:
        with pytest.raises(ConnectionError):
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                cli.send("b", "t", {"x": np.zeros(8)})
                time.sleep(0.05)
            pytest.fail("plaintext sends kept succeeding against a "
                        "TLS server")
    finally:
        cli.close()
        srv.close()


def test_tls_client_against_plaintext_server_times_out_cleanly(certs):
    """The inverse mismatch: the TLS client's handshake never gets a
    ServerHello; it must fail as an attributed ConnectionError within
    the configured timeout."""
    cli_cfg = CommCfg(timeout=2.0, tls=certs.templated_spec())
    addrs = local_addresses(["a", "b"])
    srv = SocketCommunicator("b", addrs, timeout=5.0)    # no TLS
    cli = SocketCommunicator("a", addrs, comm_cfg=cli_cfg)
    try:
        t0 = time.monotonic()
        with pytest.raises(ConnectionError) as ei:
            cli.send("b", "t", {"x": np.zeros(1)})
        assert time.monotonic() - t0 < 10.0
        assert "'b'" in str(ei.value)
    finally:
        cli.close()
        srv.close()


# ---------------------------------------------------------------------------
# bit-identity: TLS wraps the wire only
# ---------------------------------------------------------------------------


def _linreg_case():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(192, 12))
    w = rng.normal(size=(12, 2))
    y = x @ w * 0.4 + rng.normal(scale=0.05, size=(192, 2))
    ids = [f"u{i:05d}" for i in range(192)]
    master, members = vertical_partition(ids, x, y, widths=[4, 3],
                                         overlap=1.0, seed=1)
    cfg = VFLConfig(protocol="linreg", epochs=3, batch_size=48, lr=0.1,
                    seed=0, use_psi=False, pipeline_depth=1)
    return cfg, master, members


def _assert_matches_seed_trace(res):
    np.testing.assert_allclose(
        [h["loss"] for h in res["master"]["history"]],
        TRACES["linreg"]["losses"], rtol=0, atol=0)
    np.testing.assert_allclose(res["master"]["w_master"],
                               TRACES["linreg"]["w_master"],
                               rtol=0, atol=0)
    for j in range(2):
        np.testing.assert_allclose(res[f"member{j}"]["w"],
                                   TRACES["linreg"]["w_members"][j],
                                   rtol=0, atol=0)


@pytest.mark.parametrize("mode", ["socket", "grpc", "grpc_proc"])
def test_depth1_linreg_bit_identical_over_tls(mode, certs):
    """TLS changes the wire bytes, nothing above them: depth-1 runs
    over both TLS'd framings (threads and one-process-per-agent) must
    reproduce the recorded seed traces bit-identically."""
    cfg, master, members = _linreg_case()
    comm = CommCfg(timeout=60.0, tls=certs.templated_spec())
    res = run_vfl(cfg, master, members, mode=mode, comm_cfg=comm)
    _assert_matches_seed_trace(res)


def test_grpc_tls_link_shaping_composes_bit_identical(certs):
    """TLS + LinkSpec WAN shaping + sender-thread encode offload all
    compose: the shaped, encrypted, offloaded depth-1 run still equals
    the seed trace exactly (the snapshot contract holds under TLS)."""
    cfg, master, members = _linreg_case()
    comm = CommCfg(timeout=60.0, tls=certs.templated_spec(),
                   link=LinkSpec(latency_ms=2.0), encode_offload=True)
    res = run_vfl(cfg, master, members, mode="grpc", comm_cfg=comm)
    _assert_matches_seed_trace(res)
