"""The paper's example programs with small databases and parameter sets:
the shared table of the tests that run every program through a tier or
the rewriter."""

from repro.programs import (make_m0, make_orders_customer_db, make_p0,
                            make_p1, make_p2, make_sales_db, make_scan,
                            make_wilos_a, make_wilos_b, make_wilos_c,
                            make_wilos_d, make_wilos_e, make_wilos_f,
                            make_wilos_db)

# (factory, db factory, param sets) per example program
PROGRAMS = {
    "P0": (make_p0, lambda: make_orders_customer_db(300, 30), [{}] * 3),
    "P1": (make_p1, lambda: make_orders_customer_db(300, 30), [{}] * 3),
    "P2": (make_p2, lambda: make_orders_customer_db(300, 30), [{}] * 3),
    "M0": (make_m0, lambda: make_sales_db(200), [{}] * 3),
    "SCAN": (make_scan, lambda: make_wilos_db(200), [{}] * 3),
    "W_A": (make_wilos_a, lambda: make_wilos_db(120), [{}] * 2),
    "W_B": (make_wilos_b, lambda: make_wilos_db(200), [{}] * 3),
    "W_C": (make_wilos_c, lambda: make_wilos_db(120), [{}] * 2),
    "W_D": (make_wilos_d, lambda: make_wilos_db(200), [{}] * 3),
    "W_E": (make_wilos_e, lambda: make_wilos_db(200),
            [{"worklist": [0, 1, 2]}, {"worklist": [1]}, {"worklist": []}]),
    "W_F": (make_wilos_f, lambda: make_wilos_db(200), [{}] * 3),
}
