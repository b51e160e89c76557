"""The serving driver must fail with a one-line error — never a
traceback — when flags are combined with an arch the requested path
cannot serve (ISSUE 5 satellite), and the fleet spec path must validate
its input the same way."""

import json
import pathlib

import pytest

from repro.launch import serve


def test_compiled_with_unsupported_arch_errors_cleanly(capsys):
    # xlstm-350m is outside the dense DecoderLM family: --compiled has
    # no embed/run_layers_window hooks to trace (DESIGN.md §10)
    rc = serve.run(["--arch", "xlstm-350m", "--smoke", "--compiled"])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "--compiled" in err and "xlstm-350m" in err
    assert "Traceback" not in err


def test_unsupported_arch_errors_cleanly_without_compiled(capsys):
    # ... and the generic co-inference protocol mismatch is also a
    # clean error, not a constructor traceback
    rc = serve.run(["--arch", "xlstm-350m", "--smoke"])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "run_layers" in err
    assert "Traceback" not in err


def test_unsupported_model_reason_accepts_decoder_family():
    class _Decoder:
        def embed(self):
            pass

        def run_layers(self):
            pass

        def run_layers_window(self):
            pass

    assert serve.unsupported_model_reason(_Decoder(), "x", True) is None
    assert serve.unsupported_model_reason(_Decoder(), "x", False) is None
    # no run_layers at all: unservable either way
    assert "run_layers" in serve.unsupported_model_reason(
        object(), "x", False)
    # the compiled complaint is the more specific one and wins
    assert "--compiled" in serve.unsupported_model_reason(
        object(), "x", True)
    # ... and --decode needs the KV-cache decode protocol on top
    assert "--decode" in serve.unsupported_model_reason(
        _Decoder(), "x", False, decode=True)
    # ... as does --speculative, whose complaint names its own flag
    assert "--speculative" in serve.unsupported_model_reason(
        _Decoder(), "x", False, speculative=True)


@pytest.mark.parametrize("arch", ["seamless-m4t-large-v2", "xlstm-350m"])
def test_decode_with_unsupported_arch_errors_cleanly(arch, capsys):
    # encdec/hybrid archs define prefill/decode_step but not the dense
    # [layers, batch, cache_seq, kv_heads, head_dim] KV cache the decode
    # engine batches over (DESIGN.md §12) -> clean exit 2, no traceback
    rc = serve.run(["--arch", arch, "--smoke", "--decode"])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "--decode" in err and arch in err
    assert "Traceback" not in err


def test_decode_with_fcdnn_errors_cleanly(capsys):
    # fcdnn-16 ships no ModelConfig at all (it is the distortion-
    # benchmark toy); any serve invocation must fail one-line, not with
    # a build_model traceback
    rc = serve.run(["--arch", "fcdnn-16", "--smoke", "--decode"])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "fcdnn-16" in err
    assert "Traceback" not in err


@pytest.mark.parametrize("payload", ["not json {", "{}",
                                     '{"agents": []}'])
def test_fleet_spec_validation_errors_cleanly(tmp_path, payload, capsys):
    spec = tmp_path / "fleet.json"
    spec.write_text(payload)
    rc = serve.run(["--smoke", "--fleet", str(spec)])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "Traceback" not in err


def test_fleet_spec_missing_file_errors_cleanly(tmp_path, capsys):
    rc = serve.run(["--smoke", "--fleet", str(tmp_path / "nope.json")])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")


@pytest.mark.parametrize("agent", [
    {"arch": "qwen2-0.5b"},                       # missing name
    {"name": "a"},                                # missing arch
    {"name": "a", "arch": "no-such-arch"},        # unknown arch
    {"name": "a", "arch": "qwen2-0.5b",
     "env_trace": "no-such-trace"},               # unknown env trace
    {"name": "a", "arch": "qwen2-0.5b",
     "sysp": {"no_such_field": 1.0}},             # bad SystemParams field
    {"name": "a", "arch": "qwen2-0.5b",
     "t0": "fast"},                               # non-numeric budget
])
def test_fleet_spec_bad_agent_entries_error_cleanly(tmp_path, agent,
                                                    capsys):
    spec = tmp_path / "fleet.json"
    spec.write_text(json.dumps({"agents": [agent]}))
    rc = serve.run(["--smoke", "--fleet", str(spec)])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error: fleet agent")
    assert "Traceback" not in err


@pytest.mark.parametrize("payload", [
    "not json {",
    '{"no_such_key": 1}',
    '{"dt_s": -0.5}',
    '{"link_outage": {"p_fail": 2.0}}',
    '{"corruption": {"typo": 0.1}}',
])
def test_chaos_spec_validation_errors_cleanly(tmp_path, payload, capsys):
    spec = tmp_path / "chaos.json"
    spec.write_text(payload)
    rc = serve.run(["--smoke", "--chaos-trace", str(spec)])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error: cannot load chaos trace")
    assert "Traceback" not in err


def test_chaos_spec_missing_file_errors_cleanly(tmp_path, capsys):
    rc = serve.run(["--smoke", "--chaos-trace",
                     str(tmp_path / "nope.json")])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")


def test_chaos_with_sequential_engine_errors_cleanly(tmp_path, capsys):
    # the sequential engine has no queue/virtual clock to supervise
    spec = tmp_path / "chaos.json"
    spec.write_text('{"corruption": {"rate": 0.1}}')
    rc = serve.run(["--smoke", "--engine", "sequential",
                     "--chaos-trace", str(spec)])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "chaos" in err and "Traceback" not in err


def test_chaos_smoke_run_prints_resilience_line(capsys):
    # the shipped example spec must keep driving a supervised smoke run
    example = pathlib.Path(__file__).resolve().parent.parent \
        / "examples" / "chaos_spec.json"
    rc = serve.run(["--smoke", "--chaos-trace", str(example)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "resilience [supervised]:" in out
    assert "tokens lost/dup=0/0" in out


@pytest.mark.parametrize("arch", ["seamless-m4t-large-v2", "xlstm-350m"])
def test_speculative_with_unsupported_arch_errors_cleanly(arch, capsys):
    # --speculative rides the decode engine's dense KV-cache protocol;
    # non-decoder archs must die with the flag's own one-liner, not a
    # SpeculativeDecodeEngine constructor traceback (DESIGN.md §16)
    rc = serve.run(["--arch", arch, "--smoke", "--speculative"])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "--speculative" in err and arch in err
    assert "Traceback" not in err


def test_speculative_bad_lookahead_errors_cleanly(capsys):
    rc = serve.run(["--smoke", "--speculative", "--lookahead", "0"])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "--lookahead" in err and "Traceback" not in err


def test_speculative_off_ladder_draft_bits_errors_cleanly(capsys):
    # b_draft must sit on the realizable container ladder: the draft
    # weights live in the same packed int4/int8 containers as every
    # other plan, so 3-bit drafts have nowhere to live
    rc = serve.run(["--smoke", "--speculative", "--draft-bits", "3"])
    err = capsys.readouterr().err
    assert rc == 2
    assert err.startswith("error:")
    assert "draft ladder" in err and "Traceback" not in err


def test_fleet_spec_compiled_unsupported_arch_errors_cleanly(tmp_path,
                                                             capsys):
    spec = tmp_path / "fleet.json"
    spec.write_text(json.dumps({
        "compiled": True,
        "agents": [{"name": "a", "arch": "xlstm-350m",
                    "t0": 1.0, "e0": 1.0}],
    }))
    rc = serve.run(["--smoke", "--fleet", str(spec)])
    err = capsys.readouterr().err
    assert rc == 2
    assert "compiled" in err and "xlstm-350m" in err
    assert "Traceback" not in err
