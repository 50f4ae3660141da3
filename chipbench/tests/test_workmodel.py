"""The byte counts, on shapes worked out by hand."""
from chipbench import workmodel


def test_at_iteration_bytes_fig12():
    field = 208 * 44 * 46 * 4                       # 1,683,968 bytes
    assert field == 1_683_968
    got = workmodel.at_iteration_bytes(208, 44, 46, 200, 16)
    # model in and out, 200 x 16 observations, 200 saved fields out and in
    assert got == 2 * field + 200 * 16 * 4 + 2 * 200 * field
    assert 0.67e9 < got < 0.68e9


def test_at_iteration_bytes_grow_with_the_record():
    a = workmodel.at_iteration_bytes(8, 4, 4, 10, 2)
    b = workmodel.at_iteration_bytes(8, 4, 4, 20, 2)
    assert b - a == 10 * (2 * 8 * 4 * 4 * 4 + 2 * 4)
