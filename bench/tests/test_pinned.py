"""The two accepted configurations read bit for bit as they did before the
reference learned spatial layers.

At small sizes of the ``unet368`` layer list (its stated arithmetic, the
three BFP8-evicted skips with it) and of the ``yolov8n_neck`` one, the
digests of ``make_weights``, ``make_frames`` and ``forward`` (jitted, as
``rel_l2_fn`` runs it, and the control as ``control_fn`` runs it) are the
ones the 1-D reference gave; so are the work counts of the full
configurations.  ``DIGESTS`` was printed by this file run as a script on
commit 7b787cb, the last with the 1-D reference only:

    git archive 7b787cb bench | tar -x -C <dir>
    cp bench/tests/test_pinned.py <dir>/bench/tests/
    cd <dir> && JAX_PLATFORMS=cpu python bench/tests/test_pinned.py
"""
from __future__ import annotations

import functools
import hashlib
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, work  # noqa: E402

SMALL = {
    "unet368": {"positions": 256, "cin": 32, "base": 32, "levels": 5,
                "n_classes": 32},
    "yolov8n_neck": {"positions": 256, "widths": [64, 128, 256], "head": 64},
}
SEED = 2 ** 33 + 7

DIGESTS = {
    "unet368": {
        "weights":
            "8496680169dc8bfcfc7b181897513aa6597030f577368a56c8346d513e86958e",
        "names":
            "1b174a1b38f605aab43c6f964572cbb82f21293ee962fb7228d3fdf87e5d8e36",
        "frames":
            "88b9f2ce037b063d9b0e5fe97a768e8a19d451a5c80d401c5bde7387eb43dcd4",
        "forward":
            "493804a1e48bf82d6c9c992dbda79459dc41783de8d0f3a6ef08a13ca7c7ded3",
        "control":
            "3dac535e43a6acf08b6af724b690332ea79e17d79d2a0db00a6a8524420aa21a",
        "work":
            "a7e8c169e1a96bcd00dbb8dde8fa5b00a51054778ea92795d12cc966c4620b06",
    },
    "yolov8n_neck": {
        "weights":
            "399e55b898e1afdfd9c719ce74d1ca797a825a9aee5a3f14b84cfb9d0a98cdb9",
        "names":
            "c836a19f8c51b8ef701258ce274c952ad3619f72ab5a7e22315da8168a812623",
        "frames":
            "0f440be7820a350f75c4b1d0b21430f51fbc59e10508b105aa0db13660dbf58d",
        "forward":
            "056420ae3a8a48618bdf463993970b590010add8bb741733685eb274aadaf161",
        "control":
            "e6f8030b0bde9458a8b9733ef01114a11aec2c43c910b406a7bf15018b55d59e",
        "work":
            "9b957640f8fc32779a07ad20a2f158ca8f65ba69e208bc8a199de054f493780b",
    },
}


def _sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _cfg(name: str) -> dict:
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


@functools.cache
def digests(name: str) -> dict[str, str]:
    cfg = _cfg(name)
    work_counts = json.dumps(work.convs(reference.model_layers(cfg)))
    cfg["model_kwargs"] = SMALL[name]
    net = reference.model_layers(cfg)
    arith = cfg["arithmetic"]
    k_weights, k_frames = jax.random.split(reference.seed_key(SEED))
    weights = reference.make_weights(net, k_weights)
    frames = reference.make_frames(net, k_frames, (2, 2),
                                   cfg["frame_channels"])
    fwd = jax.jit(lambda w, x: reference.forward(net, w, x, arith))
    return {
        "weights": _sha(*(weights[n] for n in sorted(weights))),
        "names": hashlib.sha256(" ".join(sorted(weights)).encode())
        .hexdigest(),
        "frames": _sha(frames),
        "forward": _sha(fwd(weights, frames[0, 0]),
                        fwd(weights, frames[1, 1])),
        "control": _sha(reference.control_fn(net, arith)(weights, frames[1])),
        "work": hashlib.sha256(work_counts.encode()).hexdigest(),
    }


@pytest.mark.parametrize("name", sorted(SMALL))
@pytest.mark.parametrize("what", ["weights", "names", "frames", "forward",
                                  "control", "work"])
def test_accepted_configuration_reads_as_before(name, what):
    assert digests(name)[what] == DIGESTS[name][what]


if __name__ == "__main__":
    print(json.dumps({name: digests(name) for name in sorted(SMALL)},
                     indent=1))
