"""The bytes the payload kernels' work needs."""
from bench.work import payload_bytes


def test_index_and_mask_bytes_alone():
    assert payload_bytes(352, store_rows=320, stored_rows=0, fetch_rows=320,
                         fetched_rows=0) == 640 * 8


def test_rows_moved():
    # stored: read from the packet, written to the slot; fetched: read
    # from the slot, written to the packet, slot cleared
    assert payload_bytes(352, 0, 10, 0, 0) == 2 * 10 * 352
    assert payload_bytes(352, 0, 0, 0, 10) == 3 * 10 * 352


def test_one_pipe_step_of_the_tor_cell_is_about_a_quarter_megabyte():
    need = payload_bytes(352, store_rows=320, stored_rows=150,
                         fetch_rows=320, fetched_rows=140)
    assert 250_000 < need < 260_000
    assert need / 819e9 < 0.35e-6     # under 0.35 us at v5e HBM bandwidth
