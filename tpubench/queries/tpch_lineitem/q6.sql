SELECT SUM(l_extendedprice * l_discount)
FROM lineitem
WHERE l_shipdate >= '{date_lo}' AND l_shipdate < '{date_hi}'
  AND l_discount >= {disc_lo} AND l_discount <= {disc_hi}
  AND l_quantity < {quantity}
