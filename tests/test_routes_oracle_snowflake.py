"""The ``xla`` accumulate routes against the numpy float64 oracle on the
snowflake schemas, Retailer and TPC-DS.  Each batch runs on the dense,
compact and clustered routes, and a maintained covar batch takes one delta
tick (``routes_oracle.py``)."""

import pytest

from routes_oracle import cases, check_route


@pytest.mark.parametrize("schema_name,batch,route",
                         cases("retailer", "tpcds"))
def test_route_matches_oracle(schema_name, batch, route):
    check_route(schema_name, batch, route)
