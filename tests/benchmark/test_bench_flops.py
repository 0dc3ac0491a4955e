"""Model FLOPs of the training cells, against arithmetic by hand."""
import harness
from flops import gcn_train_step


def test_cora_by_hand():
    n, e = 2708, 10556
    layer0 = (e + n) * 1433 + 2 * (2 * n * 1433 * 16)     # agg, fwd + dW
    layer1 = 2 * (e + n) * 16 + 3 * (2 * n * 16 * 7)     # + input gradient
    assert gcn_train_step(n, e, (1433, 16, 7)) == layer0 + layer1
    assert gcn_train_step(n, e, (1433, 16, 7)) == 269_607_632


def test_arxiv_by_hand():
    n, e = 169_343, 2_315_598
    a = e + n
    total = (a * 128 + 2 * 2 * n * 128 * 256
             + 2 * a * 256 + 3 * 2 * n * 256 * 256
             + 2 * a * 256 + 3 * 2 * n * 256 * 40)
    assert gcn_train_step(n, e, (128, 256, 256, 40)) == total
    assert 100e9 < total < 105e9


def test_the_cells_configurations_give_these_shapes():
    spec = harness.load_spec()
    for name, dims in (("gcn-cora", (1433, 16, 7)),
                       ("gcn-arxiv", (128, 256, 256, 40))):
        cfg = harness.config(spec, name)
        assert (cfg["graph"]["feat_dim"], *cfg["hidden"],
                cfg["classes"]) == dims
