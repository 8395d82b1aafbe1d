"""Generate docs/api.md from the public API surface's docstrings.

The surface below IS the compatibility contract: the CI docs job
(docs/check.py) fails when a symbol listed in the generated api.md
disappears, so removals are always a conscious diff to this file.

  PYTHONPATH=src python docs/gen_api.py
"""
from __future__ import annotations

import importlib
import inspect
import pathlib
import textwrap

OUT = pathlib.Path(__file__).resolve().parent / "api.md"

# (section, module, symbols, methods-to-document-per-class)
SURFACE = [
    ("Jobs and runners", "repro.core.party",
     ["VFLJob", "run_vfl", "PartyMaster", "PartyMember", "Arbiter"],
     {"VFLJob": ["from_spec", "fit", "predict", "evaluate",
                 "serve_open", "serve_query", "serve_close",
                 "shutdown"]}),
    ("Protocol hooks", "repro.core.protocols.driver",
     ["VFLProtocol", "Driver", "Callback", "Checkpointer",
      "EarlyStopping", "StopAtStep", "MetricsStream", "EvalEveryEpoch",
      "ElasticCfg", "ExchangeCapture"],
     {"VFLProtocol": ["match", "setup", "on_batch_master",
                      "on_batch_member", "member_stage_send",
                      "member_stage_recv", "arbiter_round",
                      "predict_master", "predict_member",
                      "predict_embed", "send_embed",
                      "evaluate_master", "finalize", "state_dict",
                      "load_state_dict", "cost_profile"]}),
    ("Configuration and data", "repro.core.protocols.base",
     ["VFLConfig", "MasterData", "MemberData", "register",
      "defense_noise"], {}),
    ("Model towers", "repro.models.tower",
     ["TowerSpec", "resolve", "check_blocks", "parse_block",
      "mlp_tower", "legacy_dims_tower", "init", "apply",
      "logical_axes", "make_tower_rules", "shard_tower",
      "tower_flops"], {}),
    ("Exchange account", "repro.launch.exchange",
     ["exchange_account"], {}),
    ("Adversarial harness", "repro.attacks",
     ["AttackHarness", "gradient_direction_attack", "cluster_attack",
      "probe_attack", "run_privacy_matrix"],
     {"AttackHarness": ["run", "capture", "labels", "member_x",
                        "grad_attack", "embed_attack"]}),
    ("Communicator engine", "repro.comm.base",
     ["CommCfg", "LinkSpec", "TLSSpec", "PartyCommunicator", "Message",
      "SendFuture", "RecvFuture", "CommStats"],
     {"PartyCommunicator": ["send", "isend", "recv", "recv_any",
                            "irecv", "broadcast", "gather",
                            "flush_sends", "close"]}),
    ("Transports", "repro.comm.sock",
     ["SocketCommunicator", "local_addresses"], {}),
    ("Transports (gRPC framing)", "repro.comm.grpc",
     ["GrpcCommunicator"], {}),
    ("Typed message schema", "repro.comm.schema",
     ["message", "Field", "TypedChannel", "SchemaError"],
     {"TypedChannel": ["send", "isend", "recv", "recv_parts", "irecv",
                       "frame", "broadcast", "gather", "flush"]}),
    ("HE layer (Paillier)", "repro.core.he",
     ["keygen", "PublicKey", "PrivateKey", "encrypt_vector",
      "decrypt_vector", "packed_matvec", "RandomnessPool",
      "DecryptPool", "DecryptSession", "DecryptWorkerError"],
     {"RandomnessPool": ["start", "take", "stats", "stop"],
      "DecryptPool": ["session", "decrypt_many", "stats", "close"],
      "DecryptSession": ["submit", "gather"]}),
    ("Federated serving", "repro.serve.federated",
     ["ServeCfg", "ServeStats", "FederatedServer", "ServeFrontend",
      "ServeClient", "AdmissionError"],
     {"FederatedServer": ["start", "stop", "submit", "query"],
      "ServeClient": ["query", "stats", "close"]}),
    ("Cluster launcher", "repro.launch.cluster",
     ["ClusterSpec", "RestartPolicy", "ChaosSpec", "ServeSpec",
      "load_spec", "ClusterLauncher", "quickstart_data",
      "linreg_demo_data", "logreg_he_demo_data"],
     {"ClusterSpec": ["validate", "comm_for", "make_communicator",
                      "build_data", "restart_of", "restartable_roles"],
      "ClusterLauncher": ["run", "request_stop"]}),
    ("Test certificates", "repro.launch.certs",
     ["TestCA", "have_openssl"],
     {"TestCA": ["issue", "tls_spec", "templated_spec"]}),
]


def _sig(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return ""


def _doc(obj, indent: str = "") -> str:
    doc = inspect.getdoc(obj) or "*(undocumented)*"
    return textwrap.indent(doc, indent)


def render() -> str:
    lines = [
        "# API reference",
        "",
        "*Generated by `docs/gen_api.py` — edit docstrings, not this "
        "file. The CI docs job fails if any symbol listed here "
        "disappears from the codebase.*",
        "",
        "User guides: [transports.md](transports.md) · "
        "[protocols.md](protocols.md) · design notes: "
        "[DESIGN.md](../DESIGN.md).",
    ]
    for section, modname, symbols, methods in SURFACE:
        mod = importlib.import_module(modname)
        lines += ["", f"## {section} — `{modname}`"]
        for name in symbols:
            obj = getattr(mod, name)
            kind = "class" if inspect.isclass(obj) else "function"
            sig = "" if inspect.isclass(obj) and \
                inspect.signature(obj.__init__) is None else _sig(
                    obj.__init__ if inspect.isclass(obj) else obj)
            if inspect.isclass(obj):
                sig = sig.replace("(self, ", "(").replace("(self)", "()")
            lines += ["", f"### `{modname}.{name}`", "",
                      f"*{kind}* `{name}{sig}`", "", _doc(obj)]
            for mname in methods.get(name, []):
                m = getattr(obj, mname)
                msig = _sig(m).replace("(self, ", "(") \
                              .replace("(self)", "()")
                lines += ["", f"#### `{name}.{mname}{msig}`", "",
                          _doc(m)]
    return "\n".join(lines) + "\n"


def main() -> None:
    OUT.write_text(render())
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
