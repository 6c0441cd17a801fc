"""``flops.train_step_flops`` against counts worked by hand for a path graph
of 3 nodes (self-loops included, 7 real slots) with 5 input features."""

import flops

N, SLOTS, IN, CLASSES = 3, 7, 5, 2


def test_gcn_by_hand():
    cfg = {"layers": [{"kind": "gcn", "out": 4}, {"kind": "relu"},
                      {"kind": "dropout", "rate": 0.5},
                      {"kind": "gcn", "out": "classes"}, {"kind": "log_softmax"}]}
    # layer 0: product 2*3*5*4 = 120, aggregation 2*7*4 = 56; backward
    #   120 (weights) + 56 (aggregation), no input gradient of the features
    # layer 1: product 2*3*4*2 = 48, aggregation 2*7*2 = 28; backward
    #   48 + 48 + 28
    assert flops.train_step_flops(cfg, N, SLOTS, IN, CLASSES) == 176 + 176 + 76 + 124


def test_gat_by_hand():
    cfg = {"layers": [
        {"kind": "gat", "heads": 2, "out": 3, "concat": True, "attn_dropout": 0.6},
        {"kind": "elu"},
        {"kind": "gat", "heads": 2, "out": "classes", "concat": False, "attn_dropout": 0.6},
    ]}
    # layer 0: product 2*3*5*6 = 180, attention 4*3*6 = 72, aggregation
    #   2*7*6 = 84; backward 180 + 2*72 + 2*84
    # layer 1 (input 6 wide): product 2*3*6*4 = 144, attention 4*3*4 = 48,
    #   aggregation 2*7*4 = 56; backward 144 + 144 + 2*48 + 2*56
    assert flops.train_step_flops(cfg, N, SLOTS, IN, CLASSES) == 336 + 492 + 248 + 496


def test_padding_and_halo_copies_do_not_count():
    cfg = {"layers": [{"kind": "gcn", "out": 4}]}
    # the count takes the graph's own nodes and slots; the same graph laid
    # out in more rows (halo copies, padding) gives the same number
    assert flops.train_step_flops(cfg, N, SLOTS, IN, CLASSES) == 352
