"""Launcher CLI smoke tests: train (with checkpoint resume) and serve run
end to end on reduced configs."""
import jax
import numpy as np
import pytest


def test_train_runs_and_loss_drops(tmp_path):
    from repro.launch import train
    out = train.main([
        "--arch", "granite-moe-3b-a800m", "--reduced",
        "--steps", "6", "--batch", "2", "--seq", "32",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
        "--log-every", "3",
    ])
    assert np.isfinite(out["last_loss"])
    # resume: a second invocation continues from the final snapshot
    out2 = train.main([
        "--arch", "granite-moe-3b-a800m", "--reduced",
        "--steps", "8", "--batch", "2", "--seq", "32",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "4",
        "--log-every", "4",
    ])
    assert len(out2["losses"]) <= 3, "resume should skip completed steps"


def test_serve_generates_valid_tokens():
    from repro.launch import serve
    out = serve.main([
        "--arch", "recurrentgemma-9b", "--reduced",
        "--batch", "2", "--prompt-len", "8", "--gen", "4",
    ])
    assert out["tokens"].shape == (2, 12)


def test_bn_learn_cli():
    from repro.launch import bn_learn
    out = bn_learn.main(["--network", "stn", "--iters", "50",
                         "--samples", "200"])
    assert np.isfinite(out["score"])
    assert out["adjacency"].shape == (11, 11)


def test_bn_learn_cli_published_arities():
    """--network alarm --q alarm samples and learns ALARM at its published
    states per variable, through the fused build; a malformed --q fails
    fast."""
    from repro.data.networks import ALARM_ARITY, network_data
    from repro.launch import bn_learn
    _, data = network_data("alarm", 300, ALARM_ARITY, 3)
    assert (data.max(0) == np.asarray(ALARM_ARITY) - 1).all()
    out = bn_learn.main(["--network", "alarm", "--q", "alarm", "--s", "2",
                         "--iters", "40", "--samples", "300",
                         "--preprocess", "fused", "--cache-dir", ""])
    assert np.isfinite(out["score"])
    assert out["adjacency"].shape == (37, 37)
    with pytest.raises(SystemExit):
        bn_learn.main(["--network", "stn", "--q", "2,x", "--iters", "5"])


def test_bn_learn_cli_rejects_degenerate_windows():
    """--window 1 (no in-window move) and --window > n (would be silently
    clamped mid-trace) fail FAST with a readable argparse error."""
    from repro.launch import bn_learn
    for bad in ("1", "-3", "12"):        # stn has n=11 nodes
        with pytest.raises(SystemExit):
            bn_learn.main(["--network", "stn", "--iters", "10",
                           "--samples", "50", "--window", bad])
    # boundary: window == n is legal (delta may still reject via crossover)
    out = bn_learn.main(["--network", "stn", "--iters", "10",
                         "--samples", "50", "--window", "11"])
    assert np.isfinite(out["score"])


def test_bn_learn_cli_adaptive_and_exchange():
    """--adapt-window and --exchange-every compose through the CLI."""
    from repro.launch import bn_learn
    out = bn_learn.main(["--network", "stn", "--iters", "60", "--chains", "2",
                         "--samples", "200", "--adapt-window",
                         "--burn-in", "20", "--exchange-every", "15"])
    assert np.isfinite(out["score"])
    assert out["adaptive_windows"] == [2, 4]       # n=11 caps the set at 4
