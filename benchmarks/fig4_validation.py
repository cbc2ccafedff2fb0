"""Paper Fig 4: measured-vs-predicted TP collectives at FULL model size.

The explicit Megatron-TP engine (core/parallel_exec.py) is lowered for the
paper's actual subjects (Llama-3.2-3B / 3.1-8B / 2-13B, full layer counts) on
a 4-device TP mesh — ShapeDtypeStruct params, no allocation — and the
compiled HLO collective counts/bytes are compared against Eq. 1.  This is the
paper's validation plot as an equality check.

Runs in a subprocess so the 4-device host-platform flag stays contained.
A CPU tool (``JAX_PLATFORMS=cpu`` in the child): it compiles, it never
times a device.
"""
import json
import os
import subprocess
import sys

MODELS = ["llama32-3b", "llama31-8b", "llama2-13b"]
REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _measure():
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    import jax

    from repro.configs import get_config
    from repro.core import commodel as cm
    from repro.core import parallel_exec as px
    from repro.core.hlo_comm import parse_hlo_collectives, summarize

    out = []
    S, B, t = 128, 1, 4
    for arch in MODELS:
        cfg = get_config(arch)
        mesh = px.make_tp_mesh(t)
        fn = px.tp_prefill(cfg, mesh)
        model_params = jax.eval_shape(
            lambda: __import__("repro.models.transformer",
                               fromlist=["get_model"]).get_model(cfg).init(
                                   jax.random.PRNGKey(0)))
        toks = jax.ShapeDtypeStruct((B, S), jax.numpy.int32)
        hlo = fn.lower(model_params, toks).compile().as_text()
        meas = summarize(parse_hlo_collectives(hlo))
        pred = cm.tp_comm_ops(cfg, S, 1, t, gather_mode="allgather", batch=B)
        pred_ar = sum(o.count for o in pred if o.collective == "allreduce")
        # the CPU host backend upcasts bf16 collectives to f32 (b=4); on TPU
        # the wire dtype is bf16 (b=2, the paper's Table IV accounting)
        pred_ar_bytes = sum(o.count * o.elements * 4 for o in pred
                            if o.collective == "allreduce")
        out.append({
            "arch": arch,
            "measured_ar": meas["allreduce"]["count"],
            "predicted_ar": pred_ar,
            "measured_ar_bytes": meas["allreduce"]["msg_bytes"],
            "predicted_ar_bytes": pred_ar_bytes,
            "measured_ag": meas.get("allgather", {}).get("count", 0),
        })
    print("FIG4JSON:" + json.dumps(out))


def rows():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig4_validation", "--measure"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    out = []
    for line in r.stdout.splitlines():
        if line.startswith("FIG4JSON:"):
            for rec in json.loads(line[len("FIG4JSON:"):]):
                match = (rec["measured_ar"] == rec["predicted_ar"]
                         and rec["measured_ar_bytes"] == rec["predicted_ar_bytes"])
                out.append((f"fig4/{rec['arch']}/tp4_fullsize", 0.0,
                            f"measured_ar={rec['measured_ar']};"
                            f"predicted_ar={rec['predicted_ar']};"
                            f"ar_bytes={rec['measured_ar_bytes']};"
                            f"match={'EXACT' if match else 'MISMATCH'}"))
    if not out:
        raise RuntimeError(
            f"fig4_validation child failed: {r.stderr[-300:]}")
    return out


def main():
    print("Fig 4 — full-size measured (HLO) vs predicted (Eq.1) TP collectives")
    for r in rows():
        print(f"  {r[0]:34s} {r[2]}")


if __name__ == "__main__":
    if "--measure" in sys.argv:
        _measure()
    else:
        main()
