"""``correct`` on a tiny cell, on the CPU with the device check skipped: a
sound run passes, the fp8 control put in the program's place fails the same
comparison, and a run with the timed path broken underneath (decode state
left unchanged, a token altered where it is sampled) comes out false."""
from bench import harness as H
from bench.conftest import TINY_LIMIT

SEED = 2 ** 31 + 4242


def _run(root, **kw):
    return H.run_cell(root, "tiny.tiny", SEED, 1.0, False,
                      require_accelerator=False, **kw)


def test_sound_run_passes(tiny_root):
    out = _run(tiny_root)
    log = out["_log"]
    assert out["correct"] is True
    assert out["attempted"] == 6 and out["failed"] == 0
    assert out["checks"]["max_logit_gap"]["value"] <= TINY_LIMIT
    assert log["tokens_checked"] >= 20
    assert log["window_compiles"] == 0
    assert log["reference_module"] == "gqa" and log["engine_paged"] is True
    assert set(out["metrics"]) == {"setup_s", "ttft_mean_ms", "tbt_mean_ms"}


def test_the_control_in_the_programs_place_fails(tiny_root):
    out = _run(tiny_root, control="fp8")
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > TINY_LIMIT
    # the program's own tokens, read in the same run, pass
    assert out["_log"]["program_max_logit_gap"] <= TINY_LIMIT


def _state_unchanged(model):
    """decode returns the cache it was given: no key or value is written
    and no position advances."""
    import dataclasses

    def decode(params, inputs, cache):
        logits, _ = model.decode_fn(params, inputs, cache)
        return logits, cache

    return dataclasses.replace(model, decode_fn=decode)


def test_decode_that_keeps_its_state_fails(tiny_root):
    out = _run(tiny_root, model_hook=_state_unchanged)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_altered_token_fails(tiny_root, monkeypatch):
    import repro.serving.engine as E

    real = E.sample_batch

    def altered(logits, keys, temperature, top_k=0):
        return (real(logits, keys, temperature, top_k=top_k) + 1) \
            % logits.shape[-1]

    monkeypatch.setattr(E, "sample_batch", altered)
    out = _run(tiny_root)
    assert out["correct"] is False


def test_traced_run_reports_per_layer_metrics(tiny_root):
    out = H.run_cell(tiny_root, "tiny.tiny", SEED + 1, 1.0, True,
                     require_accelerator=False, check=False)
    assert set(out["metrics"]) == {"queue_wait_mean_ms"}
    assert out["correct"] is False          # no check made, none claimed
