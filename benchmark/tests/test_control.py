"""The rest of a run with the timed path broken underneath: the harness's
look for a chip is skipped (toy sizes, CPU, a child process) and ``correct``
has to come out false.  The controls at a lower precision are in
``test_reference.py``; the readings on the chip at the cells' own sizes are in
PERF.md."""

import pytest

import rehearse


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearse.make_copy(str(tmp_path_factory.mktemp("bench_broken")))


BROKEN = {
    "a step that returns its state unchanged": (
        "resnet50_train", 1,
        "import optax\noptax.apply_updates = lambda params, updates: params\n",
        "delta_norm_worst"),
    "the exchange between chips left out": (
        "resnet50_dp4", 4,
        "import horovod_tpu.optim.distributed_optimizer as d\n"
        "d.allreduce_gradients = lambda grads, **kw: grads\n",
        "grad_norm_worst"),
    "a part of the batch left out of the loss": (
        "resnet50_train", 1,
        "import optax\n_x = optax.softmax_cross_entropy_with_integer_labels\n"
        "optax.softmax_cross_entropy_with_integer_labels = "
        "lambda logits, y: _x(logits, y) * (y < 5)\n",
        "loss_rel"),
    "a served token altered where it is produced": (
        "mistral7b_chat", 1,
        "import horovod_tpu.models.llama as L\n_d = L.decode_chunk_paged\n"
        "def _neg(*a, **k):\n"
        "    logits, cache = _d(*a, **k)\n"
        "    return -logits, cache\n"
        "L.decode_chunk_paged = _neg\n",
        "gap_max"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_timed_path_is_not_correct(copy, fault):
    cell, devices, patch, number = BROKEN[fault]
    rc, last, out, err = rehearse.run_in_copy(copy, cell, devices=devices,
                                              extra=patch)
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last["correct"] is False
    failed = [ln for ln in out.splitlines() if "NOT CORRECT" in ln]
    assert any(f"check {number}:" in ln for ln in failed), failed


def test_the_same_runs_unbroken_are_correct(copy):
    for cell, devices in (("resnet50_train", 1), ("mistral7b_chat", 1)):
        rc, last, out, err = rehearse.run_in_copy(copy, cell,
                                                  devices=devices)
        assert rc == 0 and last["correct"] is True, out[-1500:]
